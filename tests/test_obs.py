"""Unified observability layer (obs/): Prometheus exposition golden
format, end-to-end job trace span trees, X-Request-Id round-trips, and
the no-silently-unmetered-routes gate.

The REST tests drive a real HTTP server (same harness as test_api.py);
the lease spans come from an injected device list — on the CPU test
backend the leaser is otherwise a no-op (jobs/leases.py docstring).
"""

import re
import time

import numpy as np
import pytest
import requests

from learningorchestra_tpu.api import APIServer
from learningorchestra_tpu.config import Config
from learningorchestra_tpu.jobs.leases import DeviceLeaser
from learningorchestra_tpu.obs import metrics as obs_metrics
from learningorchestra_tpu.obs import tracing as obs_tracing

PREFIX = "/api/learningOrchestra/v1"

#: One Prometheus text-exposition sample line:
#: name{labels} value  (labels optional; values incl. +Inf).
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" (\+Inf|-Inf|NaN|-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?)$"
)


@pytest.fixture(scope="module")
def api(tmp_path_factory):
    obs_metrics.reset_registry()  # this module owns a fresh registry
    tmp = tmp_path_factory.mktemp("obs_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    # Injected devices: lease spans + utilization gauges need a chip
    # pool; CPU backends discover none (tests/test_leases.py idiom).
    server.ctx.leaser = DeviceLeaser(["virt:0", "virt:1"])
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    yield base, server
    server.shutdown()


def wait_finished(base, name, timeout=120):
    deadline = time.time() + timeout
    while time.time() < deadline:
        meta = requests.get(
            f"{base}/observe/{name}", params={"timeout": 5}, timeout=30
        ).json()["metadata"]
        if meta.get("finished"):
            return meta
        if meta.get("jobState") == "failed":
            raise AssertionError(f"job failed: {meta.get('exception')}")
    raise AssertionError(f"timeout waiting for {name}")


@pytest.fixture(scope="module")
def trained_job(api):
    """One finished neural train job submitted with a client
    X-Request-Id — the fixture every trace/metrics test reads."""
    base, _server = api
    resp = requests.post(f"{base}/model/tensorflow", json={
        "modelName": "obs_mlp",
        "modulePath": "learningorchestra_tpu.models.mlp",
        "class": "MLPClassifier",
        "classParameters": {"hidden_layer_sizes": [8], "num_classes": 2},
    })
    assert resp.status_code == 201, resp.text
    wait_finished(base, "obs_mlp")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).tolist()
    y = rng.integers(0, 2, (64,)).tolist()
    resp = requests.post(
        f"{base}/train/tensorflow",
        json={
            "name": "obs_fit", "parentName": "obs_mlp", "method": "fit",
            "methodParameters": {
                "x": x, "y": y, "epochs": 3, "batch_size": 16,
            },
        },
        headers={"X-Request-Id": "req-obs-roundtrip"},
    )
    assert resp.status_code == 201, resp.text
    assert resp.headers["X-Request-Id"] == "req-obs-roundtrip"
    meta = wait_finished(base, "obs_fit")
    return base, meta


# -- Prometheus exposition golden format -------------------------------------


def test_metrics_prom_golden_format(trained_job):
    base, _meta = trained_job
    resp = requests.get(f"{base}/metrics.prom", timeout=30)
    assert resp.status_code == 200
    assert resp.headers["Content-Type"].startswith("text/plain")
    text = resp.text
    assert text.endswith("\n")

    seen_types: dict[str, str] = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram", "untyped")
            seen_types[name] = kind
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"unparseable line: {line!r}"

    # One exposition unifies ≥ 5 subsystems (the acceptance bar):
    # HTTP routes, job engine, leases, compile cache, serving, store.
    for family in (
        "lo_http_request_duration_seconds",   # HTTP per-route latency
        "lo_jobs_queue_wait_seconds",         # job engine
        "lo_jobs_queue_depth",
        "lo_lease_wait_seconds",              # chip leases
        "lo_lease_devices",
        "lo_compile_cache_events_total",      # compile cache
        "lo_serving_resident_models",         # serving
        "lo_store_wal_bytes",                 # store / replication
        "lo_replication_epoch",
    ):
        assert family in seen_types, f"missing family {family}"
    assert seen_types["lo_http_request_duration_seconds"] == "histogram"
    assert seen_types["lo_jobs_queue_wait_seconds"] == "histogram"
    assert seen_types["lo_lease_wait_seconds"] == "histogram"
    assert seen_types["lo_compile_cache_events_total"] == "counter"


def test_metrics_prom_histogram_bucket_monotonicity(trained_job):
    base, _meta = trained_job
    text = requests.get(f"{base}/metrics.prom", timeout=30).text
    bucket_re = re.compile(
        r"^(\w+)_bucket\{(.*)\} ([0-9.e+]+|\+Inf)$"
    )
    series: dict[tuple, list] = {}
    counts: dict[tuple, float] = {}
    for line in text.splitlines():
        m = bucket_re.match(line)
        if m:
            labels = dict(
                kv.split("=", 1) for kv in m.group(2).split('",')
                if "=" in kv
            )
            le = labels.pop("le").strip('"')
            key = (m.group(1), tuple(sorted(labels.items())))
            series.setdefault(key, []).append(
                (le.strip('"'), float(m.group(3)))
            )
        elif "_count{" in line:
            name, rest = line.split("_count{", 1)
            labels, value = rest.rsplit("} ", 1)
            counts[(name, labels)] = float(value)
    assert series, "no histogram buckets rendered"
    for key, buckets in series.items():
        values = [v for _le, v in buckets]
        assert values == sorted(values), (
            f"non-monotonic cumulative buckets for {key}: {buckets}"
        )
        # The +Inf bucket is rendered last and equals the series count.
        assert buckets[-1][0] == "+Inf"


def test_metrics_prom_disabled_renders_comment_only():
    registry = obs_metrics.MetricsRegistry(enabled=False)
    counter = registry.counter("c_total", labels=("k",))
    counter.inc(k="v")  # no-op when disabled
    text = registry.render_prometheus()
    assert "disabled" in text
    assert all(
        line.startswith("#") for line in text.splitlines() if line
    )


def test_registry_label_cardinality_bounded():
    registry = obs_metrics.MetricsRegistry(enabled=True, max_series=4)
    counter = registry.counter("burst_total", labels=("url",))
    for i in range(100):
        counter.inc(url=f"/fuzz/{i}")
    snap = registry.snapshot()["burst_total"]["series"]
    assert len(snap) <= 5  # 4 real series + 1 overflow
    overflow = [
        s for s in snap
        if s["labels"]["url"] == obs_metrics.OVERFLOW_LABEL
    ]
    assert overflow and overflow[0]["value"] == 96
    assert registry.series_overflows == 96


# -- job trace span tree ------------------------------------------------------


def test_trace_span_tree_for_finished_train_job(trained_job):
    base, meta = trained_job
    resp = requests.get(
        f"{base}/observability/jobs/obs_fit/trace", timeout=30
    )
    assert resp.status_code == 200, resp.text
    doc = resp.json()
    assert doc["requestId"] == "req-obs-roundtrip"
    names = [s["name"] for s in doc["spans"]]
    for expected in ("queue_wait", "job", "lease", "compile", "epoch"):
        assert expected in names, f"missing span {expected}: {names}"
    assert names.count("epoch") == 3  # one per epoch

    by_id = {s["id"]: s for s in doc["spans"]}
    job = next(s for s in doc["spans"] if s["name"] == "job")
    lease = next(s for s in doc["spans"] if s["name"] == "lease")
    fit_init = next(s for s in doc["spans"] if s["name"] == "fit_init")
    # Nesting: lease under job; fit_init and every epoch under lease;
    # the program build under the fit's set-up, where it happens.
    assert lease["parent"] == job["id"]
    for span in doc["spans"]:
        if span["name"] in ("fit_init", "epoch"):
            assert span["parent"] == lease["id"], span
        if span["name"] == "compile":
            assert span["parent"] == fit_init["id"], span
    # The rendered tree mirrors the parent links.
    roots = {node["name"] for node in doc["tree"]}
    assert roots == {"queue_wait", "job"}
    job_node = next(n for n in doc["tree"] if n["name"] == "job")
    lease_node = next(
        c for c in job_node["children"] if c["name"] == "lease"
    )
    assert {c["name"] for c in lease_node["children"]} >= {
        "fit_init", "epoch",
    }
    init_node = next(
        c for c in lease_node["children"] if c["name"] == "fit_init"
    )
    assert "compile" in {c["name"] for c in init_node["children"]}

    # Duration consistency: children nest WITHIN their parents, and
    # queue_wait + job account for the submit→finish wall time the
    # job actually took (fitTime is the fit portion of the job span).
    assert lease["durationS"] <= job["durationS"] + 0.05
    child_sum = sum(
        s["durationS"] for s in doc["spans"]
        if s["parent"] == lease["id"]
    )
    assert child_sum <= lease["durationS"] + 0.05
    assert meta["fitTime"] <= job["durationS"] + 0.05
    for span in doc["spans"]:
        assert span["end"] is not None
        assert span["end"] >= span["start"]
        parent = by_id.get(span["parent"])
        if parent is not None:
            assert span["start"] >= parent["start"] - 0.05

    # The trace persists in the execution ledger (the durable record
    # the endpoint reads), tagged with the same request id.
    rows = requests.get(
        f"{base}/train/tensorflow/obs_fit",
        params={"limit": 50}, timeout=30,
    ).json()
    ledger_traces = [
        d["trace"] for d in rows
        if d.get("docType") == "execution" and d.get("trace")
    ]
    assert ledger_traces
    assert ledger_traces[-1]["requestId"] == "req-obs-roundtrip"


def test_every_second_of_a_fit_job_has_a_named_span(trained_job):
    """ROADMAP D12: between ``job`` start and end every interval has a
    named child — parameter resolution, the fit's set-up, the epochs,
    the publish, the history rows, the terminal commit."""
    base, _meta = trained_job
    doc = requests.get(
        f"{base}/observability/jobs/obs_fit/trace", timeout=30
    ).json()
    by_id = {s["id"]: s for s in doc["spans"]}
    job = next(s for s in doc["spans"] if s["name"] == "job")

    def under_job(span):
        while span is not None and span["id"] != job["id"]:
            span = by_id.get(span["parent"])
        return span is not None

    named = [s for s in doc["spans"] if s is not job and under_job(s)]
    names = {s["name"] for s in named}
    assert {"load_artifact", "resolve_params", "lease_wait", "lease",
            "fit_init", "epoch", "publish", "store_history",
            "commit"} <= names, names
    for name in ("load_artifact", "resolve_params", "lease_wait",
                 "lease", "publish", "store_history", "commit"):
        span = next(s for s in named if s["name"] == name)
        assert span["parent"] == job["id"], span
    publish = next(s for s in named if s["name"] == "publish")
    assert publish["attrs"]["bytes"] > 0
    epochs = [s for s in named if s["name"] == "epoch"]
    assert [s["attrs"]["epoch"] for s in epochs] == [0, 1, 2]
    # What the named pieces cover of the job's wall: ``lease`` only
    # holds the fit's pieces, and a ``compile`` (with the ``cost_probe``
    # in it) and jax's own ``jit_compile`` lie inside the pieces that
    # build or first call a program, so none is counted beside them.
    covered = sum(
        s["durationS"] for s in named
        if s["name"] not in ("lease", "compile", "cost_probe",
                             "jit_compile")
    )
    assert covered >= 0.9 * job["durationS"], (
        covered, job["durationS"],
        {s["name"]: s["durationS"] for s in named},
    )
    assert covered <= job["durationS"] + 0.05


def test_publish_and_load_spans_count_the_raw_arrays(api):
    """A fit whose parameters hold an array of at least
    ``RAW_MIN_BYTES`` publishes it raw: the ``publish`` span says how
    much (``raw_bytes``, ``raw_arrays``) of its ``bytes``, and a job
    that continues from the artifact says what it read the same way in
    its ``load_artifact`` span."""
    base, _server = api
    resp = requests.post(f"{base}/model/tensorflow", json={
        "modelName": "obs_wide",
        "modulePath": "learningorchestra_tpu.models.mlp",
        "class": "MLPClassifier",
        # The 512 x 512 kernel and its two Adam moments: 1 MiB each.
        "classParameters": {"hidden_layer_sizes": [512, 512],
                            "num_classes": 2},
    })
    assert resp.status_code == 201, resp.text
    wait_finished(base, "obs_wide")
    rng = np.random.default_rng(0)
    fit = {
        "x": rng.standard_normal((32, 4)).tolist(),
        "y": rng.integers(0, 2, (32,)).tolist(),
        "epochs": 1, "batch_size": 16,
    }

    def spans(name):
        return requests.get(
            f"{base}/observability/jobs/{name}/trace", timeout=30
        ).json()["spans"]

    for name, parent in (("obs_wide_fit", "obs_wide"),
                         ("obs_wide_refit", "obs_wide_fit")):
        resp = requests.post(f"{base}/train/tensorflow", json={
            "name": name, "parentName": parent, "modelName": "obs_wide",
            "method": "fit", "methodParameters": fit,
        })
        assert resp.status_code == 201, resp.text
        wait_finished(base, name)
    publish = next(
        s for s in spans("obs_wide_fit") if s["name"] == "publish"
    )["attrs"]
    assert publish["raw_arrays"] >= 3
    assert 3 << 20 <= publish["raw_bytes"] < publish["bytes"]
    load = next(
        s for s in spans("obs_wide_refit") if s["name"] == "load_artifact"
    )["attrs"]
    assert load["parent"] == "obs_wide_fit"
    assert (load["raw_bytes"], load["raw_arrays"]) == (
        publish["raw_bytes"], publish["raw_arrays"],
    )


def test_a_streaming_fit_names_the_same_pieces(tmp_path):
    """The shard-streaming fit (``_fit_streaming``) leaves the spans
    the in-memory fit does: ``fit_init``, one with-block ``epoch`` an
    epoch, ``checkpoint_save`` for each save and the final wait."""
    import numpy as np

    from learningorchestra_tpu.models.mlp import MLPClassifier
    from learningorchestra_tpu.store.sharded import (
        ShardedDataset,
        ShardedDatasetWriter,
    )

    rng = np.random.default_rng(0)
    writer = ShardedDatasetWriter(
        tmp_path / "ds", ["a", "b", "label"], rows_per_shard=16
    )
    for a, b in rng.standard_normal((48, 2)):
        writer.append([float(a), float(b), int(a + b > 0)])
    writer.close()
    ds = ShardedDataset(tmp_path / "ds")

    trace = obs_tracing.JobTrace("streaming")
    with obs_tracing.activate(trace):
        MLPClassifier(hidden_layer_sizes=[4], num_classes=2).fit(
            ds, ds["label"], epochs=2, batch_size=16,
            checkpoint_dir=str(tmp_path / "ck"),
            checkpoint_min_interval_s=0.0,
        )
    spans = trace.to_doc()["spans"]
    names = [s["name"] for s in spans]
    assert names.count("fit_init") == 1
    epochs = [s for s in spans if s["name"] == "epoch"]
    assert [s["attrs"] for s in epochs] == [
        {"epoch": 0, "streaming": True}, {"epoch": 1, "streaming": True},
    ]
    saves = [s["attrs"] for s in spans if s["name"] == "checkpoint_save"]
    assert saves == [{"step": 1}, {"step": 2}, {"finalize": True}]
    # The shard programs are built at their first use, inside the
    # first epoch; the evaluation step in ``fit_init``.
    by_id = {s["id"]: s for s in spans}
    built_under = {
        (s["attrs"]["label"].split(":")[0], by_id[s["parent"]]["name"])
        for s in spans if s["name"] == "compile"
    }
    assert ("device_epoch", "epoch") in built_under, built_under
    assert {under for _label, under in built_under} <= {
        "fit_init", "epoch",
    }
    assert all(s["end"] is not None for s in spans)


class _Probe:
    """Stands in for ``jax.profiler.TraceAnnotation``: the names that
    were entered and left."""

    entered: list = []

    def __init__(self, name, **metadata):
        self.name, self.metadata = name, metadata

    def __enter__(self):
        _Probe.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        _Probe.entered.append("/" + self.name)
        return False

    def set_metadata(self, **metadata):
        self.metadata.update(metadata)


@pytest.mark.parametrize("with_trace", [True, False])
def test_span_is_a_profiler_annotation(monkeypatch, with_trace):
    """``span()`` enters ``lo:<name>`` whether or not a job trace is
    active, and records a JobTrace span only when one is."""
    monkeypatch.setattr(obs_tracing, "_trace_annotation", _Probe)
    monkeypatch.setattr(_Probe, "entered", [])
    trace = obs_tracing.JobTrace("j") if with_trace else None
    with obs_tracing.activate(trace):
        with obs_tracing.span("outer", k=1):
            with obs_tracing.span("inner"):
                obs_tracing.set_span_attrs(late=2)
    assert _Probe.entered == [
        "lo:outer", "lo:inner", "/lo:inner", "/lo:outer",
    ]
    if trace is None:
        assert obs_tracing.current_trace() is None
        return
    outer, inner = trace.to_doc()["spans"]
    assert (outer["name"], outer["attrs"]) == ("outer", {"k": 1})
    assert inner["parent"] == outer["id"]
    assert inner["attrs"] == {"late": 2}


def test_spans_and_phases_land_in_a_profiler_capture(tmp_path):
    """The real thing, on the CPU backend: a ``jax.profiler`` capture
    holds the program's intervals as ``lo:`` host events, a phase's
    metadata with them."""
    import jax
    from jax.profiler import ProfileData

    phases = obs_tracing.Phases("loop", ("work", "rest"))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_tracing.span("captured_span"):
            time.sleep(0.002)
        with obs_tracing.annotation("loop.turn") as turn:
            with phases("work"):
                time.sleep(0.003)
            turn.set_metadata(prompt=3, output=5)
    finally:
        jax.profiler.stop_trace()
    assert phases.total["work"] >= 0.003 and phases.total["rest"] == 0.0
    assert phases.peak["work"] == phases.total["work"]
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = {
        ev.name.split("#")[0]: ev
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:CPU")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("lo:")
    }
    assert {"lo:captured_span", "lo:loop.turn", "lo:loop.work"} \
        <= set(events)
    assert events["lo:captured_span"].duration_ns >= 2_000_000
    # metadata rides as the event's stats
    assert dict(events["lo:loop.turn"].stats) == {"prompt": 3, "output": 5}


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_each_flash_kernel_is_named_in_the_lowered_program(
        monkeypatch, kernel):
    """A device trace tells the three kernels apart by these names
    (``ops/attention.py``): each must reach the program lowered for
    the TPU."""
    import jax
    import jax.numpy as jnp
    from jax import export

    from learningorchestra_tpu.ops.attention import flash_attention

    monkeypatch.setenv("LO_TPU_FLASH_INTERPRET", "0")
    q = jnp.zeros((1, 2, 256, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))), platforms=["tpu"]
    )(q, q, q).mlir_module()
    assert text.count("tpu_custom_call") >= 3
    assert kernel in text


def test_trace_404_for_untraced_artifact(api):
    base, _server = api
    resp = requests.post(f"{base}/model/tensorflow", json={
        "modelName": "obs_untraced",
        "modulePath": "learningorchestra_tpu.models.mlp",
        "class": "MLPClassifier",
        "classParameters": {"num_classes": 2},
    })
    assert resp.status_code == 201
    # Ghost artifact → 404 from require_existing.
    assert requests.get(
        f"{base}/observability/jobs/ghost/trace", timeout=30
    ).status_code == 404


# -- X-Request-Id round trip --------------------------------------------------


def test_request_id_minted_and_echoed(api):
    base, _server = api
    r1 = requests.get(f"{base}/health", timeout=30)
    minted = r1.headers.get("X-Request-Id")
    assert minted and re.fullmatch(r"[0-9a-f]{16}", minted)
    # A fresh id per request, echoed verbatim when the client sends one.
    r2 = requests.get(f"{base}/health", timeout=30)
    assert r2.headers["X-Request-Id"] != minted
    r3 = requests.get(
        f"{base}/health", timeout=30,
        headers={"X-Request-Id": "my-id-42"},
    )
    assert r3.headers["X-Request-Id"] == "my-id-42"
    # A header-unsafe id is replaced, never echoed back.
    r4 = requests.get(
        f"{base}/health", timeout=30,
        headers={"X-Request-Id": "bad id\twith spaces"},
    )
    assert re.fullmatch(r"[0-9a-f]{16}", r4.headers["X-Request-Id"])


def test_request_id_roundtrips_submit_to_poll(trained_job):
    """The async submit → poll cycle: the id sent with the POST lands
    in the job's metadata, so every later poll GET (carrying its own
    response id) can still correlate the job to the original
    request."""
    base, meta = trained_job
    assert meta["requestId"] == "req-obs-roundtrip"
    poll = requests.get(
        f"{base}/train/tensorflow/obs_fit",
        params={"limit": 1}, timeout=30,
    )
    assert poll.json()[0]["requestId"] == "req-obs-roundtrip"
    # The poll response itself carries a (fresh) request id header.
    assert poll.headers.get("X-Request-Id")


# -- no silently unmetered routes --------------------------------------------


def _sample_path(pattern: str) -> str:
    """A concrete path matching a route pattern: named groups become a
    sample value drawn from their character class, alternations take
    their first arm, escapes unescape."""
    path = re.sub(
        r"\(\?P<\w+>\[([^\]]+)\][+*]\)",
        lambda m: "x1" if "A-Z" in m.group(1) else "1",
        pattern,
    )
    path = re.sub(r"\(\?:([A-Za-z0-9_\-]+)\|[^)]*\)", r"\1", path)
    return path.replace("\\.", ".")


def test_every_registered_route_is_metered(tmp_path):
    """Dispatch one request to every registered route and assert each
    route key shows up in the metrics registry — a new route cannot
    silently ship unmetered."""
    obs_metrics.reset_registry()
    cfg = Config()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.volume_root = str(tmp_path / "volumes")
    cfg.api.request_timeout_s = 30.0
    server = APIServer(cfg)
    try:
        routes = [
            (verb, pattern.pattern, key)
            for verb, pattern, _handler, key, _flags
            in server.router.routes
        ]
        assert len(routes) > 50  # the real table, not a stub
        for verb, compiled, key in routes:
            # compiled = "^<prefix><pattern>/?$"
            raw = compiled[len("^" + server.router.prefix):]
            raw = raw[:-len("/?$")]
            sample = _sample_path(raw)
            full = server.router.prefix + sample
            assert re.compile(compiled).match(full), (
                f"sample path {full!r} does not match its own route "
                f"{key!r} — extend _sample_path for this pattern shape"
            )
            server.handle(verb, full, {}, {})
        snap = obs_metrics.get_registry().snapshot()
        metered = {
            s["labels"]["route"]
            for s in snap["lo_http_request_duration_seconds"]["series"]
        }
        missing = {key for _v, _p, key in routes} - metered
        assert not missing, f"unmetered routes: {sorted(missing)}"
    finally:
        server.shutdown()
        obs_metrics.reset_registry()


def test_registry_reset_rebinds_live_server(tmp_path):
    """reset_registry() under a LIVE server must re-home both the push
    metrics and the pull collector — without the identity-checked
    rebind, observations keep landing on the new registry while
    /metrics.prom renders the orphaned old one."""
    obs_metrics.reset_registry()
    cfg = Config()
    cfg.store.root = str(tmp_path / "store")
    cfg.store.volume_root = str(tmp_path / "volumes")
    server = APIServer(cfg)
    try:
        server.handle("GET", PREFIX + "/health", {}, {})
        fresh = obs_metrics.reset_registry()
        server.handle("GET", PREFIX + "/health", {}, {})
        snap = fresh.snapshot()
        routes = {
            s["labels"]["route"]
            for s in snap["lo_http_request_duration_seconds"]["series"]
        }
        assert "GET /health" in routes
        status, payload = server.handle(
            "GET", PREFIX + "/metrics.prom", {}, {}
        )
        assert status == 200
        # Collector families prove the collector re-registered on the
        # fresh registry.
        assert b"lo_uptime_seconds" in payload[1]
        assert b"lo_compile_cache_events_total" in payload[1]
    finally:
        server.shutdown()
        obs_metrics.reset_registry()


# -- legacy endpoints remain views over the same instrumentation -------------


def test_legacy_metrics_json_still_serves(api):
    base, _server = api
    requests.get(f"{base}/health", timeout=30)
    metrics = requests.get(f"{base}/metrics", timeout=30).json()
    assert metrics["budget"]["request_timeout_s"] > 0
    health = metrics["routes"].get("GET /health")
    assert health and health["count"] >= 1 and health["avg_ms"] >= 0


# -- obs-off behavior ---------------------------------------------------------


def test_tracing_disabled_records_nothing():
    obs_metrics.reset_registry(enabled=False, trace_enabled=False)
    try:
        assert obs_tracing.new_trace("j") is None
        # span()/record_span() are no-ops without an active trace.
        with obs_tracing.span("anything", k="v") as sid:
            assert sid is None
        obs_tracing.record_span("loose", 0.1)
    finally:
        obs_metrics.reset_registry()


def test_monitoring_stopped_session_never_advertises_url(tmp_path):
    """probe_ready race (services/monitoring.py): stop() may win while
    the readiness probe is mid-connect — a stopped session must never
    publish a live TensorBoard URL.  The fake process never exits and
    the port only starts listening AFTER stop(), so without the
    stopped re-check the probe would publish."""
    import socket

    from learningorchestra_tpu.services import monitoring as mon

    class FakeProc:
        def poll(self):
            return None

        def terminate(self):
            pass

        def wait(self, timeout=None):
            return 0

    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    port = listener.getsockname()[1]

    service = mon.MonitoringService(str(tmp_path))
    orig_which = mon.shutil.which
    orig_popen = mon.subprocess.Popen
    orig_free_port = mon._free_port
    mon.shutil.which = lambda _name: "/usr/bin/true"
    mon.subprocess.Popen = lambda *a, **k: FakeProc()
    mon._free_port = lambda: port
    try:
        service.start("racy")
        session = service._sessions["racy"]
        assert service.stop("racy") is True
        # NOW the port opens: the probe thread (30 s budget) connects
        # on its next 0.2 s tick and must drop the publish.
        listener.listen(1)
        deadline = time.time() + 2.0
        while time.time() < deadline:
            assert session.url is None, (
                "stopped session advertised a TensorBoard URL"
            )
            time.sleep(0.1)
    finally:
        mon.shutil.which = orig_which
        mon.subprocess.Popen = orig_popen
        mon._free_port = orig_free_port
        listener.close()
        service.close()
