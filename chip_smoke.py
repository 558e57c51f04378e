"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the main path once, through the entry points a user calls: one
process boots the REST server (``APIServer``), and the stock client
(``client.Context``, plain urllib) in the same process ingests seeded
token ids, trains, predicts and serves over HTTP — BERT-base at full
width for fit / predict / resident predict, a 12-layer 768-wide
``DecoderLM`` for ``/serve/<model>/generate`` (concurrent and SSE).  On
a host with four or more chips it adds ``/train/horovod`` over the
whole mesh, two concurrent single-chip fits, and the ring-flash kernel.

    python chip_smoke.py            # on the machine with the chip

Everything runs at code defaults (no ``LO_TPU_*`` set); only the store
and volume roots point at a scratch directory, ``.chip_smoke/`` in the
checkout, removed at the end.  ``main()`` fails unless jax's first
device is a TPU, and any phase that raises fails the run: nothing here
turns an exception into a string.  The phases are functions of their
sizes so tests/test_chip_smoke.py can run them at tiny widths on the
CPU through the same REST calls.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Wall-clocks printed on the way are smoke output, not metrics.
"""

from __future__ import annotations

import json
import resource
import shutil
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from learningorchestra_tpu.api import APIServer
from learningorchestra_tpu.client import Context
from learningorchestra_tpu.config import Config

REPO = Path(__file__).resolve().parent
SCRATCH = REPO / ".chip_smoke"

_TEXT = "learningorchestra_tpu.models.text"

#: ISSUE 21's sizes: BERT-base at its defaults (L 12, H 768, A 12, mlp
#: 3072, vocab 30522) — 16 steps, of which epoch 1 pays the compile.
#: ``logit_tol``: both sides run TPU default matmul precision (f32
#: operands rounded to bf16, ~3 digits) through 12 layers, the kernel
#: and the reference rounding in different places.
BERT_SIZES = dict(
    class_parameters={}, vocab=30522, seq=128, batch=32, epochs=2,
    rows=256, predict_rows=64, serve_calls=3, min_kernel_calls=12,
    logit_tol=5e-2,
)
#: DecoderLM at BERT-base width; two steps of fit, then four generate
#: requests (three concurrent + one SSE) of 32 new tokens on a
#: 32-token prompt.  ``logit_tol`` bounds how far below the reference's
#: best logit a decoded token may sit: with near-random weights over a
#: 32k vocabulary a wrong token sits several units below, a rounding
#: tie within hundredths.
DECODE_SIZES = dict(
    class_parameters=dict(
        hidden_dim=768, num_layers=12, num_heads=12, mlp_dim=3072,
        max_len=1024,
    ),
    vocab=32000, seq=128, batch=8, rows=16, prompt=32, new_tokens=32,
    concurrent=3, logit_tol=0.25,
)


def _log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def device_report() -> dict:
    """What jax found, and the versions it runs on."""
    from importlib import metadata

    def version(dist: str):
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            return None

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
        "jax": jax.__version__,
        "jaxlib": version("jaxlib"),
        "libtpu": version("libtpu"),
    }


def cache_entries() -> dict[str, int]:
    """Entries of jax's persistent compilation cache, as this process
    has it configured, counted by program name (``jit_epoch``,
    ``jit_apply``, ...): a warm second run must add none under the
    names of the programs it only re-runs."""
    path = jax.config.jax_compilation_cache_dir
    counts: dict[str, int] = {}
    if path and Path(path).is_dir():
        for p in Path(path).iterdir():
            if p.name.endswith("-cache"):  # <program>-<key hash>-cache
                name = p.name.rsplit("-", 2)[0]
                counts[name] = counts.get(name, 0) + 1
    return dict(sorted(counts.items()))


def boot(root: Path, leaser=None) -> tuple[APIServer, Context]:
    """The in-process REST server on an ephemeral port + a client."""
    cfg = Config()
    cfg.store.root = str(root / "store")
    cfg.store.volume_root = str(root / "volumes")
    server = APIServer(cfg)
    if leaser is not None:  # tests lease the virtual CPU devices
        server.ctx.leaser = server.ctx.engine.leaser = leaser
    port = server.start_background()
    return server, Context(f"http://127.0.0.1:{port}")


# -- helpers ------------------------------------------------------------------


def _finished(ctx: Context, name: str, timeout: float = 900.0) -> dict:
    meta = ctx.observe.wait(name, timeout=timeout)
    if not meta.get("finished"):
        raise RuntimeError(f"job {name!r} did not finish: {meta}")
    return meta


def _token_csv(path: Path, *, rows: int, seq: int, vocab: int, seed: int,
               next_token_targets: bool = False) -> tuple[list, list]:
    """Seeded token ids (pad id 0 never drawn) as a CSV; returns
    (feature fields, target fields).  Zero-padded names keep positional
    order under any column sort.  ``next_token_targets`` writes the
    shifted sequence ``y000..`` (language modelling) instead of one
    class label."""
    rng = np.random.default_rng(seed)
    x = rng.integers(1, vocab, (rows, seq))
    fields = [f"t{i:03d}" for i in range(seq)]
    if next_token_targets:
        y = np.concatenate([x[:, 1:], np.zeros((rows, 1), int)], axis=1)
        targets = [f"y{i:03d}" for i in range(seq)]
    else:
        y = rng.integers(0, 2, (rows, 1))
        targets = ["label"]
    with open(path, "w") as fh:
        fh.write(",".join(fields + targets) + "\n")
        for xr, yr in zip(x, y):
            fh.write(",".join(map(str, [*xr, *yr])) + "\n")
    return fields, targets


def _ingest(ctx: Context, root: Path, name: str, **csv_kw) -> None:
    """CSV → /dataset/csv (file://) → projections ``<name>_x`` of the
    token fields and, for sequence targets, ``<name>_y``."""
    path = root / f"{name}.csv"
    fields, targets = _token_csv(path, **csv_kw)
    ctx.dataset_csv.insert(name, f"file://{path}")
    _finished(ctx, name)
    ctx.projection.create(f"{name}_x", name, fields)
    _finished(ctx, f"{name}_x")
    if len(targets) > 1:
        ctx.projection.create(f"{name}_y", name, targets)
        _finished(ctx, f"{name}_y")


def _rows(ctx: Context, service: str, name: str, n: int) -> list[dict]:
    """The first ``n`` data rows of an artifact (GET pages cap at 100)."""
    out: list[dict] = []
    while len(out) < n:
        page = ctx.search(
            service, name,
            query={"_id": {"$gte": 1}, "docType": {"$ne": "execution"}},
            limit=min(100, n - len(out)), skip=len(out),
        )
        if not page:
            break
        out.extend(page)
    return out


def _history(ctx: Context, service: str, name: str) -> list[dict]:
    """The job's per-epoch history rows; every loss must be finite."""
    docs = ctx.search(
        service, name, query={"docType": "history"}, limit=100
    )
    losses = [float(d["loss"]) for d in docs]
    if not losses or not all(np.isfinite(losses)):
        raise RuntimeError(f"{name}: losses not finite: {losses}")
    return docs


def _check_placement(meta: dict, platform: str, n: int) -> list[str]:
    """leasedDevices names ``n`` devices of ``platform`` and the params
    lived exactly there."""
    leased = meta.get("leasedDevices")
    if (
        not leased or len(set(leased)) != n or len(leased) != n
        or not all(d.startswith(f"{platform}:") for d in leased)
    ):
        raise RuntimeError(
            f"{meta.get('name')}: leasedDevices {leased!r}, expected "
            f"{n} distinct {platform} ids"
        )
    if sorted(meta.get("paramDevices") or []) != sorted(leased):
        raise RuntimeError(
            f"{meta.get('name')}: params lived on "
            f"{meta.get('paramDevices')!r}, lease says {leased!r}"
        )
    return leased


def _fit_program_kernel_calls(estimator, n: int, batch: int,
                              seq: int) -> int:
    """``tpu_custom_call`` count in the compiled epoch program the fit
    job ran.  The program comes out of the process-wide compile cache
    under the key the job built it with (a miss raises: then this is
    not the job's program) and is lowered again on shape avatars."""
    from learningorchestra_tpu.train.neural import _cached_program

    def not_cached():
        raise RuntimeError(
            "the fit's epoch program is not in the compile cache"
        )

    epoch = _cached_program(
        "device_epoch", estimator, "softmax_ce",
        shapes=(n, batch, True), builder=not_cached,
    )
    params = jax.eval_shape(
        estimator.module.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, seq), jnp.int32),
    )
    opt_state = jax.eval_shape(estimator.optimizer.init, params)
    text = epoch.lower(
        params, opt_state,
        jax.ShapeDtypeStruct((n, seq), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    ).compile().as_text()
    return text.count("tpu_custom_call")


def _resident(server: APIServer, name: str, platform: str):
    """The serving registry's entry for ``name``; its params must sit
    on a ``platform`` device."""
    from learningorchestra_tpu.jobs.leases import device_ids

    entry = server.serving.registry.peek(name)
    if entry is None:
        raise RuntimeError(f"{name!r} is not resident after serving")
    where = device_ids(entry.params)
    if not where or not all(d.startswith(f"{platform}:") for d in where):
        raise RuntimeError(
            f"resident params of {name!r} live on {where!r}, "
            f"expected {platform}"
        )
    return entry


# -- phases -------------------------------------------------------------------


def phase_bert(server: APIServer, ctx: Context, root: Path, platform: str,
               *, class_parameters: dict, vocab: int, seq: int, batch: int,
               epochs: int, rows: int, predict_rows: int,
               serve_calls: int, min_kernel_calls: int,
               logit_tol: float) -> dict:
    """/dataset/csv → /model → /train → /predict → /serve/<fit>/predict
    on ``models.text.BertModel``."""
    from learningorchestra_tpu.models.text import BertModel

    t0 = time.perf_counter()
    _ingest(ctx, root, "bert_train", rows=rows, seq=seq, vocab=vocab,
            seed=0)
    _ingest(ctx, root, "bert_new", rows=predict_rows, seq=seq,
            vocab=vocab, seed=1)
    ctx.model.create("bert", module_path=_TEXT, class_name="BertModel",
                     class_parameters=class_parameters)
    _finished(ctx, "bert")
    t_ingest = time.perf_counter() - t0

    t0 = time.perf_counter()
    ctx.train.create("bert_fit", model_name="bert", method_parameters={
        "x": "$bert_train_x", "y": "$bert_train.label",
        "epochs": epochs, "batch_size": batch,
    })
    fit = _finished(ctx, "bert_fit")
    t_fit = time.perf_counter() - t0
    history = _history(ctx, "train/tensorflow", "bert_fit")
    if len(history) != epochs:
        raise RuntimeError(f"bert_fit: {len(history)} epochs, not {epochs}")
    leased = _check_placement(fit, platform, 1)
    kernel_calls = _fit_program_kernel_calls(
        BertModel(**class_parameters), rows, batch, seq
    )
    if kernel_calls < min_kernel_calls:
        raise RuntimeError(
            f"compiled BERT train step holds {kernel_calls} "
            f"tpu_custom_call, expected >= {min_kernel_calls}: the "
            "flash kernel is not in the program the fit ran"
        )

    t0 = time.perf_counter()
    ctx.predict.create("bert_pred", parent_name="bert_fit",
                       method_parameters={"x": "$bert_new_x"})
    _finished(ctx, "bert_pred")
    t_predict = time.perf_counter() - t0
    job_logits = np.asarray([
        d["result"]
        for d in _rows(ctx, "predict/tensorflow", "bert_pred",
                       predict_rows)
    ], np.float32)
    if job_logits.shape != (predict_rows, 2) or \
            not np.isfinite(job_logits).all():
        raise RuntimeError(
            f"bert_pred: logits {job_logits.shape}, finite="
            f"{np.isfinite(job_logits).all()}"
        )

    # Resident serving: the rows the predict job saw, a few calls.  At
    # the job's row count the serving path resolves the (architecture,
    # bucket) program the job already compiled, so on one chip the
    # first call pays a re-trace and a cache load (~3 s) inside the
    # gateway's 10 s request budget; where the job's lease and the
    # registry's default device differ (a multi-chip host) it still
    # compiles for that device (~6 s).
    x_new = [
        [int(d[f"t{i:03d}"]) for i in range(seq)]
        for d in _rows(ctx, "transform/projection", "bert_new_x",
                       predict_rows)
    ]
    t0 = time.perf_counter()
    ctx.serve.load("bert_fit")
    t_load = time.perf_counter() - t0
    served, t_calls = [], []
    for _ in range(serve_calls):
        t0 = time.perf_counter()
        out = ctx.serve.predict("bert_fit", x_new)
        t_calls.append(round(time.perf_counter() - t0, 3))
        served.append(np.asarray(out["predictions"], np.float32))
    entry = _resident(server, "bert_fit", platform)
    # Reference: the same weights through the plain jnp attention (no
    # kernel), against both the job's and the resident path's logits.
    reference = np.asarray(jax.jit(
        BertModel(**class_parameters, use_flash=False).module.apply
    )(entry.params, jnp.asarray(x_new, jnp.int32)), np.float32)
    err_serve = max(float(np.abs(s - reference).max()) for s in served)
    err_job = float(np.abs(job_logits - reference).max())
    if not max(err_serve, err_job) < logit_tol:
        raise RuntimeError(
            f"BERT logits disagree with the reference: serve "
            f"{err_serve:.4g}, predict job {err_job:.4g} "
            f"(tolerance {logit_tol})"
        )
    return {
        "losses": [d["loss"] for d in history], "leasedDevices": leased,
        "paramDevices": fit["paramDevices"],
        "tpuCustomCalls": kernel_calls,
        "predictRows": int(job_logits.shape[0]),
        "serveCalls": serve_calls,
        "maxLogitErr": {"serve": err_serve, "predictJob": err_job},
        "compileCache": fit.get("compileCache"),
        "wallS": {
            "ingest": round(t_ingest, 1), "fit": round(t_fit, 1),
            "fitEpochs": [round(d["epoch_time"], 2) for d in history],
            "predictJob": round(t_predict, 1),
            "serveLoad": round(t_load, 1), "serveCalls": t_calls,
        },
    }


def phase_decode(server: APIServer, ctx: Context, root: Path, platform: str,
                 *, class_parameters: dict, vocab: int, seq: int,
                 batch: int, rows: int, prompt: int, new_tokens: int,
                 concurrent: int, logit_tol: float) -> dict:
    """Fit a ``DecoderLM`` for rows/batch steps, load it, and decode:
    ``concurrent`` greedy /serve/<model>/generate requests at once plus
    one ``stream=true`` request read as SSE."""
    from learningorchestra_tpu.models.text import DecoderLM

    params = dict(class_parameters, vocab_size=vocab)
    _ingest(ctx, root, "lm_train", rows=rows, seq=seq, vocab=vocab,
            seed=2, next_token_targets=True)
    ctx.model.create("lm", module_path=_TEXT, class_name="DecoderLM",
                     class_parameters=params)
    _finished(ctx, "lm")
    t0 = time.perf_counter()
    ctx.train.create("lm_fit", model_name="lm", method_parameters={
        "x": "$lm_train_x", "y": "$lm_train_y",
        "epochs": 1, "batch_size": batch,
    })
    fit = _finished(ctx, "lm_fit")
    t_fit = time.perf_counter() - t0
    history = _history(ctx, "train/tensorflow", "lm_fit")
    leased = _check_placement(fit, platform, 1)
    t0 = time.perf_counter()
    ctx.serve.load("lm_fit")
    t_load = time.perf_counter() - t0

    rng = np.random.default_rng(3)
    prompts = rng.integers(1, vocab, (concurrent + 1, prompt)).tolist()
    results: list = [None] * concurrent
    errors: list = []

    def one(i: int) -> None:
        try:
            results[i] = ctx.serve.generate(
                "lm_fit", [prompts[i]], max_new_tokens=new_tokens
            )
        except Exception as exc:  # noqa: BLE001 — re-raised below
            errors.append(exc)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=one, args=(i,)) for i in range(concurrent)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600.0)
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a concurrent generate request hung")
    t_concurrent = time.perf_counter() - t0
    rows_out = [r["tokens"][0] for r in results]
    for r in results:
        if len(r["newTokens"][0]) != new_tokens:
            raise RuntimeError(
                f"generate returned {len(r['newTokens'][0])} tokens, "
                f"asked for {new_tokens}"
            )

    t0 = time.perf_counter()
    events = list(ctx.serve.generate(
        "lm_fit", [prompts[-1]], max_new_tokens=new_tokens, stream=True
    ))
    t_stream = time.perf_counter() - t0
    names = [name for name, _ in events]
    streamed = [doc["t"] for name, doc in events if name == "token"]
    if names[0] != "open" or names[-1] != "done" or \
            len(streamed) != new_tokens:
        raise RuntimeError(
            f"SSE stream: events {names[:3]}..{names[-2:]}, "
            f"{len(streamed)} tokens, asked for {new_tokens}"
        )
    rows_out.append(prompts[-1] + streamed)

    # Logit-level agreement with the reference: the full (uncached,
    # kernel-free) forward over each finished row must rank every
    # decoded token at, or within rounding of, its best logit.
    entry = _resident(server, "lm_fit", platform)
    tokens = np.asarray(rows_out, np.int32)
    if tokens.shape != (concurrent + 1, prompt + new_tokens) or \
            tokens.min() < 0 or tokens.max() >= vocab:
        raise RuntimeError(f"decoded rows malformed: {tokens.shape}")
    ref_module = DecoderLM(**params).module.clone(use_flash=False)
    logits = np.asarray(jax.jit(ref_module.apply)(
        entry.params, jnp.asarray(tokens)
    ), np.float32)[:, prompt - 1:-1]  # position i predicts token i+1
    chosen = np.take_along_axis(
        logits, tokens[:, prompt:, None], axis=-1
    )[..., 0]
    gap = float((logits.max(-1) - chosen).max())
    if not gap < logit_tol:
        raise RuntimeError(
            f"decode disagrees with the reference forward: a decoded "
            f"token sits {gap:.4g} below the best logit "
            f"(tolerance {logit_tol})"
        )
    stats = server.serving.decode.stats()["models"]["lm_fit"]
    return {
        "losses": [d["loss"] for d in history], "leasedDevices": leased,
        "requests": concurrent + 1, "newTokensEach": new_tokens,
        "maxLogitGap": gap, "decodeSteps": stats["steps"],
        "pools": [
            {k: p[k] for k in ("kv", "slots", "steps")}
            for p in stats["pools"]
        ],
        "wallS": {
            "fit": round(t_fit, 1), "serveLoad": round(t_load, 1),
            "concurrent": round(t_concurrent, 1),
            "stream": round(t_stream, 1),
        },
    }


def ring_flash_check(n_devices: int = 4, *, t: int = 2048, d: int = 64,
                     tol: float = 2e-2) -> dict:
    """The ring-flash kernel under ``shard_map`` on an ``n_devices``-way
    sp axis against the unsharded reference, forward and gradients,
    full and causal.  ``tol``: bf16 inputs, probabilities rounded to
    bf16 before the second matmul — the bound the interpret-mode unit
    tests hold the same kernels to."""
    from learningorchestra_tpu.parallel.mesh import MeshSpec, build_mesh
    from learningorchestra_tpu.parallel.ring_attention import (
        reference_attention,
        ring_flash_attention,
    )

    mesh = build_mesh(
        MeshSpec(sp=n_devices), devices=jax.devices()[:n_devices]
    )
    rng = np.random.default_rng(11)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, t, 4, d)), jnp.bfloat16)
        for _ in range(3)
    )
    out: dict = {}
    for causal in (False, True):
        def run(attend, **kw):
            def loss(q, k, v):
                o = attend(q, k, v, causal=causal, **kw)
                return jnp.sum(o.astype(jnp.float32) ** 2), o
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True
            ))(q, k, v)

        (_, o), grads = run(ring_flash_attention, mesh=mesh)
        (_, ref), ref_grads = run(reference_attention)
        errs = {"fwd": float(jnp.max(jnp.abs(
            o.astype(jnp.float32) - ref.astype(jnp.float32)
        )))}
        for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
            rg = rg.astype(jnp.float32)
            errs[name] = float(
                jnp.max(jnp.abs(g.astype(jnp.float32) - rg))
                / jnp.maximum(1.0, jnp.max(jnp.abs(rg)))
            )
        if len(o.devices()) != n_devices or not max(errs.values()) < tol:
            raise RuntimeError(
                f"ring flash (causal={causal}) on {len(o.devices())} "
                f"devices: errors {errs}, tolerance {tol}"
            )
        out["causal" if causal else "full"] = errs
    return out


def phase_multichip(ctx: Context, platform: str, n_devices: int, *,
                    batch: int, ring: dict) -> dict:
    """On the artifacts ``phase_bert`` left: ``/train/horovod`` on the
    default mesh and on dp x tp=2, two concurrent single-chip fits that
    must land on the two different chips their leases name, and the
    ring-flash kernel over the sp axis."""
    report: dict = {}
    for name, mesh in (
        ("bert_dist", None),
        ("bert_dist_tp", {"dp": n_devices // 2, "tp": 2}),
    ):
        t0 = time.perf_counter()
        ctx.train_distributed.create(
            name, parent_name="bert", mesh=mesh, training_parameters={
                "x": "$bert_train_x", "y": "$bert_train.label",
                "epochs": 1, "batch_size": batch,
            },
        )
        meta = _finished(ctx, name)
        history = _history(ctx, "train/horovod", name)
        leased = _check_placement(meta, platform, n_devices)
        if meta.get("meshDevices") != n_devices or \
                sorted(meta.get("batchDevices") or []) != sorted(leased):
            raise RuntimeError(
                f"{name}: meshDevices {meta.get('meshDevices')}, "
                f"batches on {meta.get('batchDevices')!r}, expected "
                f"all of {leased!r}"
            )
        report[name] = {
            "mesh": mesh or "default",
            "losses": [d["loss"] for d in history],
            "meshDevices": meta["meshDevices"],
            "paramDevices": meta["paramDevices"],
            "batchDevices": meta["batchDevices"],
            "wallS": round(time.perf_counter() - t0, 1),
        }

    t0 = time.perf_counter()
    pair = ("bert_fit_a", "bert_fit_b")
    for name in pair:  # both submitted before either is awaited
        ctx.train.create(name, model_name="bert", method_parameters={
            "x": "$bert_train_x", "y": "$bert_train.label",
            "epochs": 1, "batch_size": batch,
        })
    metas = [_finished(ctx, name) for name in pair]
    chips = [_check_placement(m, platform, 1)[0] for m in metas]
    if chips[0] == chips[1]:
        raise RuntimeError(
            f"two concurrent fits both ran on {chips[0]}"
        )
    for name in pair:
        _history(ctx, "train/tensorflow", name)
    report["concurrentFits"] = {
        "devices": chips, "wallS": round(time.perf_counter() - t0, 1),
    }
    report["ringFlash"] = ring_flash_check(n_devices, **ring)
    return report


# -- entry --------------------------------------------------------------------


def run(device: dict, root: Path, *, bert: dict, decode: dict,
        ring: dict, leaser=None) -> None:
    """Every phase against one in-process server rooted at ``root``;
    one JSON line per phase.  ``device`` is :func:`device_report`'s."""
    platform = device["platform"]
    server, ctx = boot(root, leaser)
    try:
        # fileSizeLimit: ``ulimit -f`` in bytes, -1 for none; an
        # artifact is written as files of at most artifactPartBytes.
        _log("boot", storeBackend=type(server.ctx.documents).__name__,
             fileSizeLimit=resource.getrlimit(resource.RLIMIT_FSIZE)[0],
             artifactPartBytes=server.ctx.volumes.part_bytes,
             xlaCacheDir=jax.config.jax_compilation_cache_dir,
             xlaCache=cache_entries())
        _log("bert", **phase_bert(server, ctx, root, platform, **bert),
             xlaCache=cache_entries())
        _log("decode",
             **phase_decode(server, ctx, root, platform, **decode),
             xlaCache=cache_entries())
        if device["count"] >= 4:
            _log("multichip", **phase_multichip(
                ctx, platform, device["count"], batch=bert["batch"],
                ring=ring,
            ), xlaCache=cache_entries())
        else:
            _log("multichip", skipped=(
                f"jax.device_count() is {device['count']}; the "
                "multi-chip phase needs 4"
            ))
    finally:
        server.shutdown()


def main() -> int:
    device = device_report()
    _log("device", **device)
    if device["platform"] != "tpu":
        print(
            f"chip_smoke needs a TPU; jax found {device['platform']!r}",
            file=sys.stderr,
        )
        return 2
    shutil.rmtree(SCRATCH, ignore_errors=True)
    SCRATCH.mkdir(parents=True)
    try:
        run(device, SCRATCH, bert=BERT_SIZES, decode=DECODE_SIZES, ring={})
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
