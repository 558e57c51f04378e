"""Headline benchmark — multi-model training throughput, samples/sec/chip.

BASELINE.json's metric is "samples/sec/chip (MNIST, BERT-base)": this
prints MNIST-CNN (headline ``value``) plus BERT-base and ResNet-50
samples/sec + MFU and the host-path probes in the SAME JSON line.

One process, on the chip: ``main()`` fails unless jax's first device is
a TPU, and any phase that raises fails the run — a number from another
backend is never written under a device metric's name.  ROADMAP item
S1 replaces this file with the cell-structured benchmark.

Prints ONE JSON line: {"metric", "value", "unit", "device", ...}.
"""

from __future__ import annotations

import json
import time


#: Per-chip peak dense bf16 FLOP/s by ``device_kind`` (Google Cloud
#: documentation, "TPU v5e").  Add a chip with its source when a run on
#: it shows the kind string jax reports.
_PEAK_BF16_FLOPS = {
    # What jax reports for the v5e (chip runs, PR 21).
    "TPU v5 lite": 197e12,
}


def _peak_flops(device_kind: str) -> float:
    """Per-chip peak bf16 FLOP/s for the MFU denominator.  A device
    that is not in the table is an error, not a default."""
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on file for device kind {device_kind!r}; "
            f"known: {sorted(_PEAK_BF16_FLOPS)}"
        ) from None


def _model_flops_per_sample(est, x1) -> float:
    """Analytic fwd FLOPs from XLA's own cost model, times 3 for the
    canonical fwd+bwd estimate."""
    import jax

    fwd = jax.jit(est.module.apply).lower(
        est.params, x1
    ).compile().cost_analysis()
    return 3.0 * float(fwd["flops"])


def _flash_check() -> dict:
    """Compile + run the Pallas flash-attention kernel on the chip
    against the jnp reference; raises on a mismatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.ops.attention import (
        flash_attention, mha_reference,
    )

    rng = np.random.default_rng(0)
    b, h, t, d = 2, 4, 2048, 64
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
    mask = jnp.asarray(rng.integers(0, 2, (b, t)).astype(np.float32))
    out = jax.jit(flash_attention)(q, k, v, mask)
    ref = jax.jit(mha_reference)(q, k, v, mask)
    err = float(jnp.max(jnp.abs(
        out.astype(jnp.float32) - ref.astype(jnp.float32)
    )))
    if not err < 0.05:
        raise RuntimeError(f"flash-attention TPU mismatch: max err {err}")
    return {"flash_on_tpu": "ok", "flash_max_err": round(err, 5)}


def _fused_throughput(est, x, y, batch_size, k: int = 4) -> float:
    """Steady-state samples/s with the per-call cost cancelled.

    The per-epoch runner pays one dispatch + readback per epoch, which
    dominates sub-100 ms epochs.  Run k and 3k epochs as ONE jitted
    call each (build_fused_epochs) and time the difference — the
    constant per-call cost cancels exactly.
    """
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.train.neural import cached_fused_epochs

    n = len(x)
    loss_kind = est._resolve_loss(y)

    # Through the compiled-program cache: a re-run of the bench (or any
    # repeated fused-epoch caller with this spec) skips both traces.
    runners = {
        m: cached_fused_epochs(
            est, loss_kind, n=n, batch_size=batch_size, shuffle=True,
            epochs=m,
        )
        for m in (k, 3 * k)
    }
    xd, yd = jnp.asarray(x), jnp.asarray(y.astype("int32"))
    params, opt = est.params, est.opt_state
    key = jax.random.PRNGKey(0)

    def run(m):  # one dispatch; the scalar readback is the sync point
        nonlocal params, opt
        params, opt, metrics = runners[m](params, opt, xd, yd, key)
        return float(metrics["loss"][-1])

    best = 0.0
    run(k), run(3 * k)  # compile both
    # Two clean measurements normally; up to four so one scheduler/GC
    # hiccup during a short timed call (negative delta) costs a retry,
    # not the whole bench — the smoke's millisecond-scale calls hit
    # this where the on-chip shapes never do.
    positives = 0
    for _ in range(4):
        if positives >= 2:
            break
        t0 = time.perf_counter()
        run(k)
        t1 = time.perf_counter()
        run(3 * k)
        t2 = time.perf_counter()
        dt = (t2 - t1) - (t1 - t0)
        if dt > 0:
            positives += 1
            best = max(best, 2 * k * n / dt)
    if best <= 0:
        raise RuntimeError("fused timing produced non-positive delta")
    return best


def _bench_model(est, x, y, batch_size, peak, k: int = 4) -> dict:
    """Throughput + MFU for one estimator on the live backend."""
    import jax.numpy as jnp

    est._init_params(jnp.asarray(x[:1]))
    throughput = _fused_throughput(est, x, y, batch_size, k=k)
    per_sample = _model_flops_per_sample(est, jnp.asarray(x[:1]))
    return {
        "samples_per_sec": round(throughput, 1),
        "mfu": round(throughput * per_sample / peak, 4),
        "model_flops_per_sample": per_sample,
    }


# Shapes for the on-chip suite (BASELINE.md configs 2/4/5 scaled to one
# chip's HBM; batch sizes from the round-2 sweeps) and a structurally
# identical tiny-shape smoke used by tests/test_bench_smoke.py: the
# smoke drives the EXACT _tpu_suite / _assemble_tpu code path on CPU so
# a shape or key bug is caught before it costs chip time.  The smoke
# keeps the SAME seq values so the bert_base_seq{128,512} keys — which
# _assemble_tpu consumes by name — are produced identically.
FULL_SUITE = {
    "mnist": {"n": 16384, "bs": 1024, "k": 4},
    # (seq, batch_size, n_samples) per BERT point; kwargs shrink the
    # model for the smoke only.
    "bert": {"configs": [(128, 32, 2048), (512, 16, 512)],
             "kwargs": {}, "k": 2},
    "resnet": {"n": 512, "bs": 64, "hw": 224, "k": 2},
}
SMOKE_SUITE = {
    "mnist": {"n": 64, "bs": 32, "k": 2},
    "bert": {"configs": [(128, 4, 16), (512, 2, 4)],
             "kwargs": {"hidden_dim": 32, "num_layers": 1,
                        "num_heads": 2},
             "k": 1},
    "resnet": {"n": 8, "bs": 4, "hw": 56, "k": 1},
}


def _tpu_suite(peak, suite: dict = FULL_SUITE) -> dict:
    """MNIST headline + BERT-base + ResNet-50, all bf16 on chip."""
    import numpy as np

    from learningorchestra_tpu.models.text import BertModel
    from learningorchestra_tpu.models.vision import MnistCNN, ResNet50

    rng = np.random.default_rng(0)
    out: dict = {}

    # MNIST-CNN — headline continuity metric (bs 1024 from the round-2
    # on-chip sweep).
    mn = suite["mnist"]
    x = rng.standard_normal((mn["n"], 28, 28, 1), dtype=np.float32)
    y = rng.integers(0, 10, (mn["n"],), dtype=np.int32)
    out["mnist"] = _bench_model(MnistCNN(), x, y, mn["bs"], peak,
                                k=mn["k"])

    # BERT-base fine-tune shape (config 4): seq 128 primary; the seq-512
    # point (where the flash kernel pays off in-model) rides along.
    bert_cfg = suite["bert"]

    def bench_bert(seq, bs, n):
        tok = rng.integers(0, 30522, (n, seq), dtype=np.int32)
        lab = rng.integers(0, 2, (n,), dtype=np.int32)
        est = BertModel(max_len=seq, **bert_cfg["kwargs"])
        return {
            "batch_size": bs,
            **_bench_model(est, tok, lab, bs, peak, k=bert_cfg["k"]),
        }

    for seq, bs, n in bert_cfg["configs"]:
        out[f"bert_base_seq{seq}"] = bench_bert(seq, bs, n)

    # ResNet-50 / ImageNet shape (config 5, one-chip slice).
    rn = suite["resnet"]

    def bench_resnet():
        xi = rng.standard_normal((rn["n"], rn["hw"], rn["hw"], 3),
                                 dtype=np.float32)
        yi = rng.integers(0, 1000, (rn["n"],), dtype=np.int32)
        return {
            "batch_size": rn["bs"],
            **_bench_model(ResNet50(), xi, yi, rn["bs"], peak,
                           k=rn["k"]),
        }

    out["resnet50"] = bench_resnet()
    return out


def _assemble_tpu(suite: dict) -> tuple[float, dict]:
    """Fold a _tpu_suite result into (headline throughput, extra JSON
    fields) — the exact shape prior rounds' BENCH records use."""
    suite = dict(suite)
    mnist = suite.pop("mnist")
    throughput = mnist["samples_per_sec"]
    extra: dict = {}
    # Keep the headline model's MFU fields at top level (prior
    # rounds' JSON shape) alongside the per-model sub-dicts.
    for key in ("mfu", "model_flops_per_sample"):
        if key in mnist:
            extra[key] = mnist[key]
    extra.update(suite)
    extra["bert_mfu"] = extra["bert_base_seq128"]["mfu"]
    return throughput, extra


def _compile_cache_probe() -> dict:
    """Cold-vs-warm second-job submit→first-step latency through the
    compiled-program cache (train/compile_cache.py).

    Two FRESH estimator instances with an identical spec — exactly the
    repeated-REST-job shape: the first pays trace + compile, the second
    must resolve every program from the cache (hits > 0, misses == 0)
    and reach its first step strictly faster.  Small fixed shape so the
    probe costs seconds on any backend; f32 pinned for CPU parity.
    """
    import numpy as np

    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.train import compile_cache

    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 16)).astype(np.float32)
    y = rng.integers(0, 2, (256,)).astype(np.int32)

    def one_job():
        est = MLPClassifier(hidden_layer_sizes=[32], num_classes=2)
        est.compute_dtype = "float32"
        t0 = time.perf_counter()
        est.fit(x, y, epochs=1, batch_size=64, shuffle=True)
        return time.perf_counter() - t0

    before = compile_cache.counters_snapshot()
    cold = one_job()
    mid = compile_cache.counters_snapshot()
    warm = one_job()
    warm_delta = compile_cache.delta_since(mid)
    total = compile_cache.delta_since(before)
    return {
        "cold_submit_to_first_step_s": round(cold, 4),
        "warm_submit_to_first_step_s": round(warm, 4),
        "warm_speedup": round(cold / warm, 2) if warm > 0 else None,
        "warm_hits": warm_delta["hits"],
        "warm_misses": warm_delta["misses"],
        "trace_time_s": total["traceTimeS"],
    }


def _warmboot_probe(rounds: int = 3) -> dict:
    """Durable-warm-start A/B (train/aot_store.py): first-dispatch
    latency into a FRESH compile cache, cold (trace + XLA compile)
    vs pre-warmed from an AOT-serialized executable on disk.

    Subsystem probe per ROADMAP guidance, not the noisy headline
    metric: each side is best-of-``rounds`` tight loops against its
    own fresh ``CompiledProgramCache`` — the cold side builds through
    a brand-new ``jax.jit`` wrapper every round (re-trace +
    re-compile, the restart bill), the warm side restores the SAME
    program fingerprint through the store's deserialize-and-load
    path.  The store lives in a temp dir, installed/uninstalled via
    ``reset_store`` so the probe leaves process state untouched.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.train import aot_store
    from learningorchestra_tpu.train import compile_cache as cc

    rng = np.random.default_rng(0)
    n_features = 64
    est = MLPClassifier(hidden_layer_sizes=[32], num_classes=8)
    est.compute_dtype = "float32"
    est._init_params(jnp.asarray(
        rng.standard_normal((1, n_features)).astype(np.float32)
    ))
    params = est.params
    module = est.module
    x = jnp.asarray(
        rng.standard_normal((16, n_features)).astype(np.float32)
    )
    key = cc.apply_program_key(module, rows=16)
    label = "warmboot:b16"

    def first_dispatch(cache) -> float:
        t0 = time.perf_counter()
        apply = cache.get_or_build(
            key, lambda: jax.jit(module.apply), label=label
        )
        jax.block_until_ready(apply(params, x))
        return time.perf_counter() - t0

    tmp = tempfile.mkdtemp(prefix="lo-warmboot-")
    try:
        # Populate the store once — the "previous process".
        compiled = jax.jit(module.apply).lower(params, x).compile()
        seed = aot_store.AOTExecutableStore(
            tmp, max_entries=8, max_bytes=1 << 30
        )
        seed.offer(key, aot_store.serialize(compiled), label=label)

        colds, warms, aot_hits = [], [], 0
        for _ in range(rounds):
            aot_store.reset_store()  # no store → cold build path
            colds.append(first_dispatch(
                cc.CompiledProgramCache(max_entries=8)
            ))
        for _ in range(rounds):
            aot_store.reset_store(
                root=tmp, max_entries=8, max_bytes=1 << 30
            )
            warms.append(first_dispatch(
                cc.CompiledProgramCache(max_entries=8)
            ))
            aot_hits += aot_store.get_store().hits
    finally:
        aot_store.reset_store()
        shutil.rmtree(tmp, ignore_errors=True)

    cold = min(colds)
    warm = min(warms)
    return {
        "cold_first_dispatch_s": round(cold, 4),
        "prewarmed_first_dispatch_s": round(warm, 4),
        "speedup": round(cold / warm, 2) if warm > 0 else None,
        "aot_hits": aot_hits,
        "rounds": rounds,
    }


def _mpmd_probe(
    pp: int = 2,
    hidden: int = 128,
    seq: int = 64,
    layers: int = 4,
    micro: int = 4,
    batch: int = 32,
    steps: int = 5,
) -> dict:
    """MPMD pipeline dispatch A/B (parallel/mpmd.py): per-stage
    programs host-dispatched under 1F1B vs the SAME math as ONE
    monolithic jitted program (the SPMD whole-pipeline shape).

    Two numbers matter.  (1) Cold compile: the first MPMD fit traces
    N-per-stage programs into the process-wide compile cache; a
    SECOND fit (fresh model, same shapes — the next job) must hit
    every per-stage entry with ZERO misses, while a fresh monolithic
    ``jax.jit`` wrapper re-pays its whole-pipeline compile.  That
    re-fit delta is the MPMD cold-compile advantage the README
    quotes.  (2) Steady state: best-of step latency staged/monolithic
    — the host-dispatch overhead bound (acceptance: <= 1.10 on CPU;
    the model is sized so per-stage compute amortizes the host loop).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from learningorchestra_tpu.parallel.pipeline import (
        PipelinedTransformer,
        sequential_loss,
    )
    from learningorchestra_tpu.train import compile_cache as cc

    rng = np.random.default_rng(0)
    vocab = 256
    x = rng.integers(1, vocab, size=(batch, seq)).astype(np.int32)
    y = rng.integers(0, 2, size=(batch,)).astype(np.int32)
    mask = np.ones(batch, np.float32)
    kw = dict(
        vocab_size=vocab, hidden_dim=hidden, num_layers=layers,
        num_heads=4, pp=pp, max_len=seq, compute_dtype="float32",
        n_microbatches=micro, seed=0,
    )
    cache = cc.get_cache()

    def staged_fit_once():
        model = PipelinedTransformer(schedule="mpmd", **kw)
        model._init_params(jnp.asarray(x[:1]))
        engine = model._engine()
        t0 = time.perf_counter()
        metrics, _ = engine.train_batch(x, y, mask)
        jax.block_until_ready(metrics)
        return engine, time.perf_counter() - t0

    pre = cache.stats()
    engine, staged_cold_s = staged_fit_once()
    mid = cache.stats()
    engine2, staged_refit_s = staged_fit_once()
    post = cache.stats()
    first_fit_misses = mid["misses"] - pre["misses"]
    refit_misses = post["misses"] - mid["misses"]

    # Monolithic reference: identical init + math, one jitted program.
    model = PipelinedTransformer(schedule="mpmd", **kw)
    x0 = jnp.asarray(x[:1])
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(kw["seed"]), 3)
    eparams = model._embed.init(k0, x0)
    h0 = model._embed.apply(eparams, x0)
    sparams = jax.vmap(
        lambda k: model._stage.init(k, h0, x0 != 0)
    )(jax.random.split(k1, pp))
    hparams = model._head.init(k2, h0)
    seq_fn = sequential_loss(
        model._embed.apply, model._stage.apply, model._head.apply,
        model._loss_fn, n_stages=pp,
    )
    opt = model.optimizer
    params = (eparams, sparams, hparams)
    state = opt.init(params)

    def make_mono_step():
        @jax.jit
        def mono_step(params, state, xb, yb, mb):
            (loss, _), grads = jax.value_and_grad(
                lambda p: seq_fn(*p, xb, yb, mb), has_aux=True
            )(params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        return mono_step

    xb, yb = jnp.asarray(x), jnp.asarray(y)
    mb = jnp.asarray(mask)
    mono_step = make_mono_step()
    t0 = time.perf_counter()
    params, state, loss = mono_step(params, state, xb, yb, mb)
    jax.block_until_ready(loss)
    mono_cold_s = time.perf_counter() - t0
    # A new jit wrapper = the next job's monolithic bill (re-trace +
    # re-compile; no per-stage cache entries to hit).
    mono_step2 = make_mono_step()
    t0 = time.perf_counter()
    params, state, loss = mono_step2(params, state, xb, yb, mb)
    jax.block_until_ready(loss)
    mono_refit_s = time.perf_counter() - t0

    staged_steady = min(
        _timed(lambda: jax.block_until_ready(
            engine2.train_batch(x, y, mask)[0]
        )) for _ in range(steps)
    )

    def mono_once():
        nonlocal params, state
        params, state, loss = mono_step(params, state, xb, yb, mb)
        jax.block_until_ready(loss)

    mono_steady = min(_timed(mono_once) for _ in range(steps))

    return {
        "pp": pp, "micro": micro, "batch": batch,
        "staged_cold_compile_s": round(staged_cold_s, 4),
        "staged_refit_s": round(staged_refit_s, 4),
        "first_fit_misses": first_fit_misses,
        "refit_misses": refit_misses,
        "monolithic_cold_compile_s": round(mono_cold_s, 4),
        "monolithic_refit_s": round(mono_refit_s, 4),
        "refit_speedup_vs_monolithic": round(
            mono_refit_s / staged_refit_s, 2
        ) if staged_refit_s > 0 else None,
        "staged_steady_step_s": round(staged_steady, 4),
        "monolithic_steady_step_s": round(mono_steady, 4),
        "steady_overhead_ratio": round(
            staged_steady / mono_steady, 3
        ) if mono_steady > 0 else None,
    }


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _serving_probe(
    n_features: int = 64,
    hidden: tuple = (32,),
    n_sequential: int = 64,
    n_concurrent: int = 512,
    concurrency: int = 16,
    max_batch: int = 16,
    flush_ms: float = 2.0,
) -> dict:
    """Online-serving probe: sequential single-request predict vs
    request-coalescing concurrent throughput through the serving
    MicroBatcher (serve/), plus p50/p99 request latency under
    concurrency.

    The sequential baseline runs through the SAME batcher machinery
    (same thread handoff, same bucket padding) with a ZERO flush
    deadline — the best an unbatched per-request server can do.  The
    concurrent window runs the deployment's actual coalescing policy
    (``flush_ms`` deadline), so the speedup measures what shipping the
    micro-batcher buys: one padded dispatch amortized over every
    request in flight.  Every shape bucket is compiled in a warm-up
    pass first, so compile misses are bounded by the bucket set and
    the timed windows measure steady state.

    Defaults are sized for the CPU bench box: a TINY model (batching
    amortizes per-dispatch overhead, which is the serving win on both
    CPU and a remote-TPU link; a compute-bound model on 2 cores just
    measures matmul scaling), ``concurrency == max_batch`` (so a full
    backlog short-circuits the flush wait), and best-of-N windows on
    both sides (a shared box's scheduler stalls must not bank a fake
    ratio — same discipline as _fused_throughput).
    """
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.serve.batcher import MicroBatcher
    from learningorchestra_tpu.serve.bucketing import bucket_sizes
    from learningorchestra_tpu.train import compile_cache as cc

    rng = np.random.default_rng(0)
    est = MLPClassifier(
        hidden_layer_sizes=list(hidden), num_classes=8
    )
    est.compute_dtype = "float32"
    est._init_params(
        jnp.asarray(rng.standard_normal((1, n_features)).astype(np.float32))
    )
    params = jax.device_put(est.params)
    module = est.module

    def dispatch(padded):
        apply = cc.get_cache().get_or_build(
            cc.apply_program_key(module, rows=padded.shape[0]),
            lambda: jax.jit(module.apply),
            label=f"bench-serve:b{padded.shape[0]}",
        )
        return apply(params, jnp.asarray(padded))

    before = cc.counters_snapshot()
    row = rng.standard_normal((1, n_features)).astype(np.float32)

    # Best-of-N windows for BOTH sides: the bench can share a noisy
    # box, and one descheduled window must not bank a fake ratio
    # (same discipline as _fused_throughput's retry loop).
    seq = MicroBatcher(
        dispatch, max_batch=max_batch, max_queue=1 << 14, flush_ms=0.0,
        name="bench-seq",
    )
    try:
        # Warm every bucket (sequential submits never coalesce, so
        # each lands exactly its own bucket) — compiles happen HERE,
        # not inside a timed window.
        for b in bucket_sizes(max_batch):
            seq.submit(np.repeat(row, b, axis=0))
        seq_rps = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n_sequential):
                seq.submit(row)
            seq_rps = max(
                seq_rps,
                n_sequential / (time.perf_counter() - t0),
            )
    finally:
        seq.close()

    conc = MicroBatcher(
        dispatch, max_batch=max_batch, max_queue=1 << 14,
        flush_ms=flush_ms, name="bench-conc",
    )
    try:
        latencies: list = []
        lock = threading.Lock()
        per_thread = max(1, n_concurrent // concurrency)

        def worker():
            for _ in range(per_thread):
                t1 = time.perf_counter()
                conc.submit(row)
                dt = time.perf_counter() - t1
                with lock:
                    latencies.append(dt)

        conc_rps = 0.0
        for _ in range(4):
            threads = [
                threading.Thread(target=worker)
                for _ in range(concurrency)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            conc_rps = max(
                conc_rps,
                per_thread * concurrency
                / (time.perf_counter() - t0),
            )
        stats = conc.stats()
    finally:
        conc.close()
    delta = cc.delta_since(before)
    latencies.sort()

    def pct(q):
        return round(
            latencies[min(len(latencies) - 1, int(q * len(latencies)))]
            * 1e3, 3,
        )

    return {
        "sequential_rps": round(seq_rps, 1),
        "concurrent_rps": round(conc_rps, 1),
        "coalescing_speedup": round(conc_rps / seq_rps, 2),
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
        "batch_occupancy": stats["batchOccupancy"],
        "bucket_histogram": stats["bucketHistogram"],
        # Misses bounded by the bucket set, never by request count —
        # the shape-bucketing contract the serving path guarantees.
        "compile_misses": delta["misses"],
        "buckets_possible": len(bucket_sizes(max_batch)),
    }


def _tight_best_of(fn, m: int = 5000, reps: int = 7) -> float:
    """Per-call seconds, BEST of ``reps`` windows: scheduler/steal
    noise only ever ADDS time, so the minimum is the robust estimator
    — the shared tight-loop discipline of the obs/faults/costs
    probes."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(m):
            fn()
        best = min(best, (time.perf_counter() - t0) / m)
    return best


def _obs_probe(n_jobs: int = 60, rounds: int = 3) -> dict:
    """Observability-overhead probe: what the obs layer (metrics +
    tracing, the deployed default) costs per dispatched job, against
    the system's real dispatch path with LO_TPU_OBS_ENABLED=0
    semantics.

    Two measurements, deliberately split:

    - **A/B windows** (context + denominator): alternating off/on
      rounds, each driving ``n_jobs`` function jobs through the FULL
      dispatch path — APIServer.handle POST → validation → metadata
      create → engine submit → job run → completion — exactly what
      "dispatch throughput" means to a client of this server
      (~5 ms/job on the CPU bench box).  On a shared 2-core box,
      IDENTICAL-config windows differ by ±8% (measured: off-vs-off
      swings -8%..+6%), so the window rps bound the truth but cannot
      resolve a ~50 µs/job effect; each side keeps its best window
      (noise only ever adds time).
    - **Direct cost** (the verdict's numerator): tight-loop timings
      of exactly the per-job obs work — the full trace lifecycle
      (create, queue-wait span, job span begin/activate/end, to_doc),
      the engine + HTTP metric ops, and the ledger write delta from
      carrying the trace doc.  ``overhead_pct`` is that total over
      the best OFF window's per-job dispatch time.  Tight loops are
      stable to ~1 µs where A/B windows are not.

    The acceptance bar is < 5% dispatch-throughput cost with obs on —
    beyond that means a hot-path regression in obs/, not box noise.
    """
    import tempfile
    from pathlib import Path

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.config import Config
    from learningorchestra_tpu.jobs.engine import _job_metrics
    from learningorchestra_tpu.obs import metrics as obs_metrics
    from learningorchestra_tpu.obs import tracing as obs_tracing
    from learningorchestra_tpu.store import ArtifactStore, DocumentStore

    prefix = "/api/learningOrchestra/v1"

    def one_window(enabled: bool) -> float:
        """One API-level window → per-job dispatch seconds
        (POST accepted → job finished, pipelined over n_jobs)."""
        obs_metrics.reset_registry(
            enabled=enabled, trace_enabled=enabled
        )
        with tempfile.TemporaryDirectory() as td:
            cfg = Config()
            cfg.store.root = str(Path(td) / "store")
            cfg.store.volume_root = str(Path(td) / "volumes")
            server = APIServer(cfg)
            try:
                read = server.ctx.artifacts.metadata.read
                t0 = time.perf_counter()
                for i in range(n_jobs):
                    status, payload = server.handle(
                        "POST", prefix + "/function/python",
                        {"name": f"f{i}", "function": "response = 1"},
                        {},
                    )
                    assert status == 201, payload
                deadline = time.time() + 120
                while time.time() < deadline:
                    metas = [read(f"f{i}") or {} for i in range(n_jobs)]
                    if all(m.get("finished") for m in metas):
                        break
                    time.sleep(0.01)
                else:
                    raise RuntimeError("obs probe window timed out")
                dt = time.perf_counter() - t0
            finally:
                server.shutdown()
        return dt / n_jobs

    def tight(fn, m: int = 400, reps: int = 6) -> float:
        return _tight_best_of(fn, m=m, reps=reps)

    try:
        one_window(True)  # warm-up: imports, allocator, store paths
        off_s, on_s = [], []
        for _ in range(rounds):
            off_s.append(one_window(False))
            on_s.append(one_window(True))
        off_med = min(off_s)
        on_med = min(on_s)

        # -- direct per-job obs cost, obs ON ---------------------------
        obs_metrics.reset_registry(enabled=True, trace_enabled=True)

        def trace_lifecycle():
            trace = obs_tracing.new_trace("probe")
            trace.add_span("queue_wait", 0.0, 0.001,
                           attrs={"class": "bench"})
            sid = trace.begin("job")
            with obs_tracing.activate(trace, sid):
                pass
            trace.end(sid)
            trace.to_doc()

        trace_us = tight(trace_lifecycle) * 1e6
        reg = obs_metrics.get_registry()
        http_hist = reg.histogram("probe_http_seconds", labels=("route",))
        http_total = reg.counter(
            "probe_http_total", labels=("route", "status")
        )
        http_max = reg.gauge("probe_http_max_ms", labels=("route",))

        def metric_ops():
            # Engine-side (queue-wait observe + terminal counter) plus
            # HTTP-side (_record_metric's histogram/counter/max) — the
            # full per-dispatch metric footprint.
            h, c = _job_metrics()
            h.observe(0.003, job_class="bench")
            c.inc(job_class="bench", state="finished")
            http_hist.observe(0.005, route="POST /function/python")
            http_total.inc(route="POST /function/python", status="2xx")
            http_max.set_max(5.0, route="POST /function/python")

        metrics_us = tight(metric_ops) * 1e6

        trace_doc = obs_tracing.JobTrace("probe")
        trace_doc.add_span("queue_wait", 0.0, 0.001)
        sid = trace_doc.begin("job")
        trace_doc.end(sid)
        doc = trace_doc.to_doc()
        with tempfile.TemporaryDirectory() as td:
            store = DocumentStore(Path(td) / "store")
            try:
                arts = ArtifactStore(store)
                arts.metadata.create("probe", "bench/obs")
                bare_us = tight(
                    lambda: arts.ledger.record("probe", state="finished"),
                    m=300,
                ) * 1e6
                with_us = tight(
                    lambda: arts.ledger.record(
                        "probe", state="finished", trace=doc
                    ),
                    m=300,
                ) * 1e6
            finally:
                store.close()
        ledger_us = max(0.0, with_us - bare_us)
    finally:
        obs_metrics.reset_registry()  # back to config-driven defaults

    total_us = trace_us + metrics_us + ledger_us
    dispatch_us = off_med * 1e6
    return {
        "dispatch_rps_obs_on": round(1.0 / on_med, 1),
        "dispatch_rps_obs_off": round(1.0 / off_med, 1),
        "obs_cost_us_per_job": {
            "trace": round(trace_us, 2),
            "metrics": round(metrics_us, 2),
            "ledger_trace": round(ledger_us, 2),
            "total": round(total_us, 2),
        },
        "dispatch_us_per_job": round(dispatch_us, 1),
        "overhead_pct": round(total_us / dispatch_us * 100.0, 2),
    }


def _faults_probe() -> dict:
    """Fault-plane disabled-path cost, pinned as a SUBSYSTEM number.

    The chaos probes (``faults.hit``) sit on every WAL append, HTTP
    dispatch, lease acquisition and train epoch, so the plane's claim
    — "disabled, it costs one truthiness check" — must be a measured
    number, not a docstring.  A/B windows over the full dispatch path
    cannot resolve a ~100 ns effect on this box (identical-config
    windows swing ±8%); tight-loop best-of timings can, so the banked
    verdict is the per-hit cost over the cheapest REAL operation that
    carries a probe (a durable-off WAL append), not a noise-dominated
    headline throughput delta.

    Three per-hit numbers:

    - ``disabled_ns``  — nothing armed (the deployed default);
    - ``armed_other_ns`` — a drill running on a DIFFERENT point (a
      chaos drill must not tax unrelated hot paths: this path takes
      the plane lock and misses the dict);
    - ``armed_pass_ns`` — the armed point itself deciding "don't
      fire" (rate/after bookkeeping under the lock).
    """
    import tempfile
    from pathlib import Path

    from learningorchestra_tpu import faults
    from learningorchestra_tpu.store import DocumentStore

    tight = _tight_best_of

    faults.reset()
    try:
        disabled_ns = tight(
            lambda: faults.hit("engine.dispatch")
        ) * 1e9
        # A schedule armed on another point: every OTHER hot path now
        # pays lock + dict miss per probe.
        faults.arm("train.epoch", "delay", after=1_000_000_000)
        armed_other_ns = tight(
            lambda: faults.hit("engine.dispatch")
        ) * 1e9
        # The armed point itself, scheduled never to fire.
        armed_pass_ns = tight(
            lambda: faults.hit("train.epoch")
        ) * 1e9
        faults.reset()

        # Realistic denominator: the cheapest hot operation carrying a
        # probe — one durable-off WAL append through the real store.
        with tempfile.TemporaryDirectory() as td:
            store = DocumentStore(Path(td) / "store")
            try:
                wal_append_us = tight(
                    lambda: store.insert_one("probe", {"v": 1}),
                    m=2000,
                ) * 1e6
            finally:
                store.close()
    finally:
        faults.reset()

    return {
        "hit_disabled_ns": round(disabled_ns, 1),
        "hit_armed_other_point_ns": round(armed_other_ns, 1),
        "hit_armed_pass_ns": round(armed_pass_ns, 1),
        "wal_append_us": round(wal_append_us, 2),
        "disabled_share_of_wal_append_pct": round(
            disabled_ns / 1e3 / wal_append_us * 100.0, 3
        ),
    }


def _journal_probe() -> dict:
    """Job-journal overhead on the submit/dispatch path, pinned as a
    SUBSYSTEM number (the acceptance bar: journal appends < 2% of a
    minimal job dispatch).

    The journal group-commits: the submit/dispatch hot path only
    ENQUEUES slim records (the flusher thread writes FIFO batches
    through the store WAL off-path), so the on-path overhead is the
    enqueue cost, not the WAL write.

    - ``append_us`` — one lifecycle-record enqueue (what the
      dispatch path pays journaling ``running``);
    - ``submit_pair_us`` — the ``submitted``+``queued`` pair enqueue
      (what ``submit()`` pays);
    - ``dispatch_us`` — a minimal no-op job end to end (submit →
      result) on a journal-less engine, the denominator;
    - ``appends_share_of_dispatch_pct`` — the submit/dispatch-path
      share: (submit pair + running append) / dispatch — the
      acceptance number;
    - ``job_life_share_pct`` — all four events (submit pair,
      running, terminal) over dispatch, for context.
    """
    import tempfile
    from pathlib import Path

    from learningorchestra_tpu.jobs import JobEngine, JobJournal
    from learningorchestra_tpu.store import ArtifactStore, DocumentStore

    tight = _tight_best_of
    with tempfile.TemporaryDirectory() as td:
        store = DocumentStore(Path(td) / "store")
        journal = None
        try:
            journal = JobJournal(store, Path(td) / "store")
            append_us = tight(
                lambda: journal.append("running", "probe", attempt=1),
                m=2000,
            ) * 1e6
            submit_pair_us = tight(
                lambda: journal.record_submit(
                    "probe", job_class="bench", method="run",
                ),
                m=2000,
            ) * 1e6

            arts = ArtifactStore(store)
            eng = JobEngine(arts, max_workers=1)

            def one_dispatch():
                eng.submit(
                    "bench_job2", lambda: 1, job_class="bench"
                ).result(timeout=30)
                eng._futures.pop("bench_job2", None)

            arts.metadata.create("bench_job2", "function/python")
            dispatch_us = tight(one_dispatch, m=50, reps=5) * 1e6
            eng.shutdown(wait=True)
        finally:
            # Journal first: its flusher must finish draining into
            # the store's WAL handles before they close.
            if journal is not None:
                journal.close()
            store.close()
    return {
        "append_us": round(append_us, 2),
        "submit_pair_us": round(submit_pair_us, 2),
        "dispatch_us": round(dispatch_us, 1),
        "appends_share_of_dispatch_pct": round(
            (submit_pair_us + append_us) / dispatch_us * 100.0, 3
        ),
        "job_life_share_pct": round(
            (submit_pair_us + 2 * append_us) / dispatch_us * 100.0,
            3,
        ),
    }


def _claim_probe() -> dict:
    """Scale-out control-plane overhead on the dispatch path, pinned
    as a SUBSYSTEM number (the acceptance bar: claim + release +
    amortized heartbeat ≤ 5% of a minimal job dispatch).

    The coordinator pays a cross-process flock + WAL refresh per
    operation, so unlike the journal (pure in-process enqueue) its
    cost is dominated by the filesystem round-trip:

    - ``claim_us`` — steady-state owner re-claim (what a preemption
      retry or recovered dispatch pays);
    - ``cycle_us`` — a fresh claim + release pair (what every
      clustered dispatch pays end to end);
    - ``heartbeat_us`` — one lease renewal over an engine doc and a
      live claim (amortized: runs every ``heartbeat_s`` OFF the
      dispatch path, included for context);
    - ``dispatch_us`` — a minimal no-op job end to end on a
      cluster-less engine, the denominator;
    - ``claim_share_of_dispatch_pct`` — the acceptance number: the
      per-dispatch hot-path share (heartbeat renewals run OFF this
      path on the daemon), bar ≤ 5%;
    - ``cycle_share_of_dispatch_pct`` — fresh claim + release over
      dispatch, the worst-case first-dispatch share, for context.
    """
    import tempfile
    from pathlib import Path

    from learningorchestra_tpu.jobs import JobEngine
    from learningorchestra_tpu.jobs.cluster import ClusterCoordinator
    from learningorchestra_tpu.store import ArtifactStore, DocumentStore

    tight = _tight_best_of
    with tempfile.TemporaryDirectory() as td:
        root = Path(td) / "store"
        store = DocumentStore(root)
        coord = ClusterCoordinator(
            store, root, engine_id="bench",
            heartbeat_s=3600.0, ttl_s=3600.0, sweep_s=3600.0,
        )
        try:
            # Huge intervals + no join(): the daemons stay parked, so
            # the tight loops measure the operations, not contention.
            coord.claim("probe_owned")
            claim_us = tight(
                lambda: coord.claim("probe_owned"), m=300, reps=5
            ) * 1e6
            heartbeat_us = tight(coord.heartbeat, m=300, reps=5) * 1e6

            def cycle():
                coord.claim("probe_cycle")
                coord.release("probe_cycle")

            cycle_us = tight(cycle, m=150, reps=5) * 1e6

            arts = ArtifactStore(store)
            eng = JobEngine(arts, max_workers=1)

            def one_dispatch():
                eng.submit(
                    "bench_job3", lambda: 1, job_class="bench"
                ).result(timeout=30)
                eng._futures.pop("bench_job3", None)

            arts.metadata.create("bench_job3", "function/python")
            dispatch_us = tight(one_dispatch, m=50, reps=5) * 1e6
            eng.shutdown(wait=True)
        finally:
            coord.close()
            store.close()
    return {
        "claim_us": round(claim_us, 2),
        "cycle_us": round(cycle_us, 2),
        "heartbeat_us": round(heartbeat_us, 2),
        "dispatch_us": round(dispatch_us, 1),
        "claim_share_of_dispatch_pct": round(
            claim_us / dispatch_us * 100.0, 3
        ),
        "cycle_share_of_dispatch_pct": round(
            cycle_us / dispatch_us * 100.0, 3
        ),
    }


def _costs_probe() -> dict:
    """Per-dispatch cost-accounting hook cost, pinned as a SUBSYSTEM
    number (the ROADMAP bench caveat: headline A/B windows on this box
    cannot resolve sub-µs effects; tight-loop best-of can).

    The hook sits on every serving dispatch (serve/service.py
    ``_dispatch``) and every train epoch.  Three per-hit numbers:

    - ``disabled_ns`` — LO_TPU_COSTS_ENABLED=0 (one config check, the
      path a deployment that opts out pays);
    - ``sampled_out_ns`` — enabled but the stride skips this dispatch
      (``will_record``: lock + counter, no sync, no record);
    - ``recorded_ns`` — the full sampled-in path, exactly the serving
      dispatch's call shape (stride + ledger record across
      totals/model/bucket).

    Denominator: one REAL serving dispatch — a single-row predict
    through a live MicroBatcher (enqueue → worker wake → jitted apply
    → result handoff, flush_ms=0), the narrowest interval the hook
    brackets in production.  Coalesced batches amortize the hook
    further (it fires per DISPATCH, not per request).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.config import CostsConfig
    from learningorchestra_tpu.obs import costs
    from learningorchestra_tpu.serve.batcher import MicroBatcher

    tight = _tight_best_of

    try:
        # Disabled: the deployment-opt-out path (one config check).
        costs.reset(CostsConfig(enabled=False))
        disabled_ns = tight(costs.enabled) * 1e9

        # Enabled, thinned to 1-in-100: the common sampled-out hit.
        costs.reset(CostsConfig(enabled=True, sample=0.01))
        led = costs.devtime()
        sampled_out_ns = tight(lambda: led.will_record("m")) * 1e9

        # Enabled, full-rate record — the serve _dispatch call shape.
        costs.reset(CostsConfig(enabled=True, sample=1.0))
        led = costs.devtime()

        def full_hit():
            w = led.will_record("m")
            if w:
                led.record_model(w, 1e-4, 1e6, 1e6, "m", 16)

        recorded_ns = tight(full_hit) * 1e9

        # Denominator: the real serving dispatch round-trip.
        from learningorchestra_tpu.models.mlp import MLPClassifier

        est = MLPClassifier(hidden_layer_sizes=[128], num_classes=4)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 64)).astype(np.float32)
        est.fit(x, rng.integers(0, 4, (64,)), epochs=1, batch_size=64)
        apply = jax.jit(est.module.apply)

        batcher = MicroBatcher(
            lambda padded: apply(est.params, jnp.asarray(padded)),
            max_batch=64, max_queue=256, flush_ms=0.0, name="bench",
        )
        row = x[:1]
        try:
            batcher.submit(row)  # warm the bucket-1 executable
            dispatch_us = tight(
                lambda: batcher.submit(row), m=300, reps=5
            ) * 1e6
        finally:
            batcher.close()
    finally:
        costs.reset()

    return {
        "hook_disabled_ns": round(disabled_ns, 1),
        "hook_sampled_out_ns": round(sampled_out_ns, 1),
        "hook_recorded_ns": round(recorded_ns, 1),
        "serving_dispatch_us": round(dispatch_us, 2),
        "recorded_share_of_dispatch_pct": round(
            recorded_ns / 1e3 / dispatch_us * 100.0, 3
        ),
        "disabled_share_of_dispatch_pct": round(
            disabled_ns / 1e3 / dispatch_us * 100.0, 4
        ),
    }


def _slo_probe() -> dict:
    """Rollup/SLO-plane probe: what the time dimension costs, as
    tight-loop best-of SUBSYSTEM numbers (the ROADMAP bench caveat).

    The plane touches the serving hot path at exactly ONE point — the
    per-model predict-latency histogram observation in
    ``ServingService.predict`` — so that is the per-dispatch number
    the <1% acceptance bound applies to.  The rollup tick and the
    alert evaluation run on the daemon's own clock (every
    ``LO_TPU_ROLLUP_TICK_S``, default 10 s), never per request; their
    cost is banked raw plus amortized against the tick interval (the
    fraction of one core the daemon consumes).

    The registry is populated to a realistic working set first (HTTP
    routes, job classes, serving series) — an empty-registry tick
    would flatter every number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.config import RollupConfig, SLOConfig
    from learningorchestra_tpu.obs import metrics as obs_metrics
    from learningorchestra_tpu.obs import rollup as obs_rollup
    from learningorchestra_tpu.obs import slo as obs_slo
    from learningorchestra_tpu.serve.batcher import MicroBatcher

    tight = _tight_best_of

    try:
        reg = obs_metrics.reset_registry()
        # Representative registry: 12 routes x 2 status classes with
        # latency histograms, 4 job classes, 2 served models.
        http_total = reg.counter(
            "lo_http_requests_total", "b", labels=("route", "status")
        )
        http_hist = reg.histogram(
            "lo_http_request_duration_seconds", "b", labels=("route",)
        )
        for i in range(12):
            http_total.inc(500, route=f"GET /r{i}", status="2xx")
            http_total.inc(3, route=f"GET /r{i}", status="5xx")
            for v in (0.002, 0.02, 0.2):
                http_hist.observe(v, route=f"GET /r{i}")
        jobs_total = reg.counter(
            "lo_jobs_total", "b", labels=("job_class", "state")
        )
        for cls in ("train", "tune", "predict", "default"):
            jobs_total.inc(40, job_class=cls, state="finished")
            jobs_total.inc(1, job_class=cls, state="failed")
        predict_hist = reg.histogram(
            "lo_serving_predict_duration_seconds", "b",
            labels=("model",),
        )
        for model in ("m0", "m1"):
            for v in (0.001, 0.004, 0.05):
                predict_hist.observe(v, model=model)

        tick_s_default = RollupConfig().tick_s
        engine = obs_rollup.reset_engine(
            RollupConfig(tick_s=0.0)  # manual tick; thread off
        )
        service = obs_slo.reset_service(SLOConfig())
        engine.tick()  # warm: series created, SLO instances minted

        # One full tick = snapshot ingest + SLO evaluation riding it.
        tick_us = tight(engine.tick, m=300, reps=5) * 1e6
        # Alert evaluation alone (every objective x instance).
        eval_us = tight(
            lambda: service.evaluate(engine), m=500, reps=5
        ) * 1e6
        # The ONLY per-dispatch hook this plane adds — measured in
        # its real call shape (serve.service._predict_hist: registry
        # identity check + observe).
        from learningorchestra_tpu.serve.service import _PredictHist

        hook = _PredictHist()
        hook.observe(0.004, "m0")  # warm the handle
        observe_ns = tight(lambda: hook.observe(0.004, "m0")) * 1e9

        # Denominator: the same real single-row serving dispatch the
        # costs probe uses.
        from learningorchestra_tpu.models.mlp import MLPClassifier

        est = MLPClassifier(hidden_layer_sizes=[128], num_classes=4)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 64)).astype(np.float32)
        est.fit(x, rng.integers(0, 4, (64,)), epochs=1, batch_size=64)
        apply = jax.jit(est.module.apply)
        batcher = MicroBatcher(
            lambda padded: apply(est.params, jnp.asarray(padded)),
            max_batch=64, max_queue=256, flush_ms=0.0, name="bench",
        )
        row = x[:1]
        try:
            batcher.submit(row)  # warm the bucket-1 executable
            dispatch_us = tight(
                lambda: batcher.submit(row), m=300, reps=5
            ) * 1e6
        finally:
            batcher.close()
    finally:
        obs_rollup.reset_engine()
        obs_slo.reset_service()
        obs_metrics.reset_registry()

    return {
        "rollup_tick_us": round(tick_us, 2),
        "slo_eval_us": round(eval_us, 2),
        "predict_observe_ns": round(observe_ns, 1),
        "serving_dispatch_us": round(dispatch_us, 2),
        # The per-dispatch acceptance bound: the predict histogram
        # observation is the plane's only hot-path addition.
        "per_dispatch_share_pct": round(
            observe_ns / 1e3 / dispatch_us * 100.0, 3
        ),
        # Daemon duty cycle at the default tick interval: the
        # fraction of one core the rollup+SLO clock consumes.
        "tick_duty_cycle_pct": round(
            tick_us / (tick_s_default * 1e6) * 100.0, 4
        ),
    }


def _flight_probe() -> dict:
    """Flight-recorder probe: what the always-on incident timeline
    costs on the hot path, as tight-loop best-of SUBSYSTEM numbers.

    Three appends measured: DISABLED (the deployed ``record()`` cost
    when ``LO_TPU_FLIGHT_ENABLED=0`` — one module-global check),
    ENABLED (dict build + GIL-atomic deque append, the always-on
    default), and the TRIGGER path (what a hot-path caller pays for
    ``bundle.trigger`` once the debounce window has it returning
    immediately — the alert-storm steady state; actual assembly is
    file IO on its own thread and never rides a request).  The
    acceptance bound is the enabled append against the same real
    single-row serving dispatch the costs/SLO probes use: ≤ 1%.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from learningorchestra_tpu.config import (
        BundleConfig,
        FlightConfig,
    )
    from learningorchestra_tpu.obs import bundle as obs_bundle
    from learningorchestra_tpu.obs import flight as obs_flight
    from learningorchestra_tpu.serve.batcher import MicroBatcher

    tight = _tight_best_of

    try:
        # Disabled: the LO_TPU_FLIGHT_ENABLED=0 deployment's cost.
        obs_flight.reset(FlightConfig(enabled=False))
        disabled_ns = tight(
            lambda: obs_flight.record(
                "http", "request", route="GET /r", status=200,
            )
        ) * 1e9

        # Enabled (the default): a full-shape HTTP event into a
        # warm ring — eviction is in steady state, as deployed.
        obs_flight.reset(FlightConfig())
        for _ in range(600):
            obs_flight.record(
                "http", "request", route="GET /r", status=200,
            )
        enabled_ns = tight(
            lambda: obs_flight.record(
                "http", "request", route="GET /r", status=200,
            )
        ) * 1e9

        # Trigger path: debounced module-level bundle.trigger — the
        # per-call cost once an incident already landed its bundle.
        with tempfile.TemporaryDirectory() as tmp:
            svc = obs_bundle.reset_service(
                BundleConfig(dir=tmp, debounce_s=3600.0),
                providers={},
            )
            obs_bundle.trigger("bench")  # lands the first bundle
            deadline = time.perf_counter() + 10.0
            while (svc.status()["building"]
                   and time.perf_counter() < deadline):
                time.sleep(0.01)  # assembly is on its own thread
            trigger_ns = tight(
                lambda: obs_bundle.trigger("bench"), m=2000,
            ) * 1e9
            # Drop the singleton BEFORE the tempdir: a late assembly
            # must not race the directory teardown.
            obs_bundle.reset_service()

        # Denominator: the same real single-row serving dispatch the
        # costs/SLO probes use.
        from learningorchestra_tpu.models.mlp import MLPClassifier

        est = MLPClassifier(hidden_layer_sizes=[128], num_classes=4)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 64)).astype(np.float32)
        est.fit(x, rng.integers(0, 4, (64,)), epochs=1, batch_size=64)
        apply = jax.jit(est.module.apply)
        batcher = MicroBatcher(
            lambda padded: apply(est.params, jnp.asarray(padded)),
            max_batch=64, max_queue=256, flush_ms=0.0, name="bench",
        )
        row = x[:1]
        try:
            batcher.submit(row)  # warm the bucket-1 executable
            dispatch_us = tight(
                lambda: batcher.submit(row), m=300, reps=5
            ) * 1e6
        finally:
            batcher.close()
    finally:
        obs_flight.reset()
        obs_bundle.reset_service()

    return {
        "record_disabled_ns": round(disabled_ns, 1),
        "record_enabled_ns": round(enabled_ns, 1),
        "trigger_debounced_ns": round(trigger_ns, 1),
        "serving_dispatch_us": round(dispatch_us, 2),
        # The acceptance bound: the always-on enabled append against
        # one real single-row dispatch.
        "per_dispatch_share_pct": round(
            enabled_ns / 1e3 / dispatch_us * 100.0, 3
        ),
    }


def _decode_probe(
    n_prompts: int = 16,
    max_slots: int = 16,
    hidden: int = 128,
    layers: int = 2,
    heads: int = 4,
    vocab: int = 256,
    t0: int = 8,
    max_new: int = 56,
) -> dict:
    """Streaming-decode probe: continuous batching through the decode
    engine vs sequential solo ``generate``, tokens/sec best-of (the
    ROADMAP bench caveat: tight-loop subsystem numbers, not the
    noise-dominated headline).

    The sequential baseline is the pre-engine serving reality — one
    jitted decode scan per request, warm compile cache — which is
    also the fairest one: it pipelines its own steps through async
    dispatch exactly like the engine's lazy pools do, so the measured
    speedup isolates what SHARING a step across in-flight sequences
    buys.  The engine side submits every prompt at once and lets
    admission pack the slot buckets.  A mid-flight TTFT sample rides
    along: with a stream already generating, a newly admitted stream's
    first token must arrive within a handful of shared steps — the
    continuous-batching latency story next to the throughput one.
    """
    import numpy as np

    from learningorchestra_tpu.config import Config
    from learningorchestra_tpu.models.text import DecoderLM
    from learningorchestra_tpu.serve.decode import DecodeEngine
    from learningorchestra_tpu.serve.registry import ModelRegistry

    total = t0 + max_new
    rng = np.random.default_rng(0)
    est = DecoderLM(
        vocab_size=vocab, hidden_dim=hidden, num_layers=layers,
        num_heads=heads, max_len=total, seed=0,
    )
    est.compute_dtype = "float32"
    x = rng.integers(1, vocab, size=(8, total - 2)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.zeros((8, 1), np.int32)], axis=1)
    est.fit(x, y, epochs=1, batch_size=8)
    prompts = rng.integers(
        1, vocab, size=(n_prompts, t0)
    ).astype(np.int32)

    # Sequential baseline, warm solo program, best-of windows.
    est.generate(prompts[:1], max_new_tokens=max_new)
    seq_tok_s = 0.0
    for _ in range(3):
        t_start = time.perf_counter()
        for i in range(n_prompts):
            est.generate(prompts[i:i + 1], max_new_tokens=max_new)
        dt = time.perf_counter() - t_start
        seq_tok_s = max(seq_tok_s, n_prompts * max_new / dt)

    # The engine needs only config + registry residency: a stub
    # service around a REAL ModelRegistry (no fleet, no HTTP).
    cfg = Config()
    cfg.decode.max_slots = max_slots
    cfg.decode.max_new_tokens = max(
        cfg.decode.max_new_tokens, max_new
    )
    cfg.decode.max_streams = max(
        cfg.decode.max_streams, n_prompts + 2
    )

    class _Ctx:
        config = cfg

    class _Svc:
        ctx = _Ctx()
        registry = ModelRegistry(lambda name: est)

    engine = DecodeEngine(_Svc())
    try:
        # Warm pass compiles the slot-bucket ladder once.
        engine.generate(
            "bench_lm", prompts.tolist(), max_new_tokens=max_new
        )
        eng_tok_s, out = 0.0, None
        for _ in range(3):
            t_start = time.perf_counter()
            out = engine.generate(
                "bench_lm", prompts.tolist(), max_new_tokens=max_new
            )
            dt = time.perf_counter() - t_start
            eng_tok_s = max(eng_tok_s, n_prompts * max_new / dt)
        solo = np.asarray(
            est.generate(prompts[:1], max_new_tokens=max_new)
        )[0].tolist()
        bit_identical = out["tokens"][0] == solo

        # Mid-flight admission TTFT.
        bg = engine.generate(
            "bench_lm", prompts[0].tolist(),
            max_new_tokens=max_new, stream=True,
        )
        deadline = time.time() + 30
        while not bg.tokens and time.time() < deadline:
            time.sleep(0.002)
        mid = engine.generate(
            "bench_lm", prompts[1].tolist(),
            max_new_tokens=max_new, stream=True,
        )
        mid.wait_done(60)
        bg.wait_done(60)
        ttft_ms = mid.summary().get("ttftMs")
    finally:
        engine.close()
    return {
        "sequential_tok_s": round(seq_tok_s, 1),
        "engine_tok_s": round(eng_tok_s, 1),
        "continuous_batching_speedup": round(
            eng_tok_s / seq_tok_s, 2
        ) if seq_tok_s else None,
        "midflight_ttft_ms": ttft_ms,
        "bit_identical_to_solo": bool(bit_identical),
        "n_prompts": n_prompts,
        "max_new": max_new,
    }


def _fleet_probe(
    n_requests: int = 384,
    concurrency: int = 16,
    row_service_us: float = 500.0,
) -> dict:
    """Fleet-serving probe: router decision cost + 1→2 replica
    throughput, both as tight-loop best-of numbers (the ROADMAP bench
    caveat: this box's headline metric is noise-dominated; subsystem
    probes are the durable evidence).

    **Router overhead** — per-decision cost of ``P2CRouter.choose``
    over a static depth snapshot, best of N loops.  The contract:
    routing must be noise next to a batcher flush (µs against the
    flush deadline's milliseconds), or the fleet taxes the
    single-replica path it exists to relieve.

    **Replica scaling A/B** — the same concurrent load driven through
    a real ReplicaSet at 1 then 2 replicas, with a dispatch that
    sleeps ``row_service_us`` per PADDED row.  The sleep stands in for
    a throughput-saturated device: on this 2-core CPU box a
    compute-bound dispatch would measure matmul core-sharing, not
    replica-level scaling, while a device-bound per-row cost (the TPU
    serving reality — the batcher worker blocks on the chip, and a
    saturated chip's batch time scales with rows) overlaps across
    replicas exactly as chips do.  A per-DISPATCH cost would be the
    wrong model here: the coalescer absorbs concurrency into bigger
    batches and one replica looks infinitely scalable.  Best-of
    windows on both sides.
    """
    import threading

    import numpy as np

    from learningorchestra_tpu.config import ServeConfig
    from learningorchestra_tpu.jobs.leases import DeviceLeaser
    from learningorchestra_tpu.serve.fleet import P2CRouter, ReplicaSet

    # -- router decision cost ------------------------------------------------
    router = P2CRouter(seed=0)
    depths = [3, 0, 5, 1]
    best = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        for _ in range(20_000):
            router.choose(depths)
        best = min(best, (time.perf_counter() - t0) / 20_000)
    decision_us = best * 1e6

    # -- 1→2 replica throughput A/B ------------------------------------------
    row = np.ones((1, 8), np.float32)

    def run_fleet(n_replicas: int) -> float:
        leaser = DeviceLeaser([f"probe:{i}" for i in range(n_replicas)])
        rs = ReplicaSet(
            "bench-fleet",
            ServeConfig(max_batch=32, max_queue=1 << 14, flush_ms=0.5),
            leaser,
            lambda replica: (
                lambda padded: (
                    time.sleep(padded.shape[0] * row_service_us / 1e6),
                    padded,
                )[1]
            ),
            min_replicas=1,
            max_replicas=n_replicas,
        )
        try:
            rs.scale_to(n_replicas)
            per_thread = max(1, n_requests // concurrency)

            def worker():
                for _ in range(per_thread):
                    rs.submit(row)

            rps = 0.0
            for _ in range(3):
                threads = [
                    threading.Thread(target=worker)
                    for _ in range(concurrency)
                ]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                rps = max(
                    rps,
                    per_thread * concurrency
                    / (time.perf_counter() - t0),
                )
            return rps
        finally:
            rs.close()

    rps_1 = run_fleet(1)
    rps_2 = run_fleet(2)
    return {
        "router_decision_us": round(decision_us, 3),
        "replicas1_rps": round(rps_1, 1),
        "replicas2_rps": round(rps_2, 1),
        "replica_scaling_speedup": round(rps_2 / rps_1, 2),
        "row_service_us": row_service_us,
    }


#: Host-path probes, by the key each lands under in the result line.
_PROBES = {
    "compile_cache": _compile_cache_probe,
    "serving": _serving_probe,
    "obs": _obs_probe,
    "faults": _faults_probe,
    "journal": _journal_probe,
    "cluster": _claim_probe,
    "fleet": _fleet_probe,
    "decode": _decode_probe,
    "costs": _costs_probe,
    "slo": _slo_probe,
    "flight": _flight_probe,
    "warmboot": _warmboot_probe,
}


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; jax found {dev.platform!r} "
            f"({dev.device_kind})"
        )
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }
    print(json.dumps({"device": device}), flush=True)
    throughput, extra = _assemble_tpu(
        _tpu_suite(_peak_flops(dev.device_kind))
    )
    extra.update(_flash_check())
    for key, probe in _PROBES.items():
        extra[key] = probe()
    # One device per pipeline stage: two stages need two chips.
    extra["mpmd"] = _mpmd_probe() if device["count"] >= 2 else (
        f"skipped: needs 2 devices, jax found {device['count']}"
    )
    print(json.dumps({
        "metric": "mnist_cnn_train_samples_per_sec_per_chip_tpu",
        "value": round(throughput, 1),
        "unit": "samples/sec/chip",
        "device": device,
        **extra,
    }))


if __name__ == "__main__":
    main()
