"""Force-execute bench.py's on-chip suite path on CPU.

``bench._tpu_suite`` only runs when the live backend is a TPU, which
means a shape or key bug would otherwise surface only on the chip —
costing chip time.  This smoke drives the EXACT same code path
(``_tpu_suite`` → ``_bench_model`` → ``build_fused_epochs`` →
``_assemble_tpu`` JSON assembly) with structurally identical tiny
shapes (``bench.SMOKE_SUITE`` keeps the same seq values so the
``bert_base_seq{128,512}`` keys that ``_assemble_tpu`` consumes by name
are produced identically) and a fake TPU peak so the MFU fields
assemble as they would on chip.
"""

import json

import pytest

import bench


@pytest.mark.slow  # ResNet-50 fwd+bwd compile dominates (~2.5 min)
def test_tpu_suite_smoke_end_to_end():
    peak = 197e12  # fake per-chip peak: exercises the MFU assembly
    suite = bench._tpu_suite(peak, bench.SMOKE_SUITE)

    for key in ("mnist", "bert_base_seq128", "bert_base_seq512",
                "resnet50"):
        assert isinstance(suite[key], dict), f"{key}: {suite[key]}"

    throughput, extra = bench._assemble_tpu(suite)
    assert throughput > 0
    # Headline MFU fields hoisted to top level, riders as sub-dicts.
    assert "mfu" in extra and "model_flops_per_sample" in extra
    for rider in ("bert_base_seq128", "bert_base_seq512", "resnet50"):
        d = extra[rider]
        assert d["samples_per_sec"] > 0
        assert d["batch_size"] > 0
        # Tiny-model MFU rounds to 0.0000 against a real chip's peak —
        # the schema check is that the field exists, is in range, and
        # the FLOP estimate behind it is live.
        assert 0 <= d["mfu"] < 1, (rider, d)
        assert d["model_flops_per_sample"] > 0, (rider, d)
    # bert_mfu is the headline BERT point's MFU, surfaced by key.
    assert extra["bert_mfu"] == extra["bert_base_seq128"]["mfu"]
    # The final record must be JSON-serializable exactly as main() emits.
    record = {"metric": "mnist_cnn_train_samples_per_sec_per_chip_tpu",
              "value": round(throughput, 1), "unit": "samples/sec/chip",
              **extra}
    json.loads(json.dumps(record))


def test_serving_probe_smoke():
    """Drive bench._serving_probe's exact code path at tiny scale: the
    record must assemble JSON-clean, latencies must be ordered, and
    compile misses must be bounded by the bucket set — the
    shape-bucketing contract the full probe asserts on chip."""
    out = bench._serving_probe(
        n_features=8, hidden=(16,), n_sequential=8, n_concurrent=32,
        concurrency=8, max_batch=8,
    )
    assert out["sequential_rps"] > 0
    assert out["concurrent_rps"] > 0
    assert out["coalescing_speedup"] > 0
    assert 0 <= out["p50_ms"] <= out["p99_ms"]
    assert 0 < out["batch_occupancy"] <= 1
    # Misses bounded by buckets, never request count (48 requests ran).
    assert out["compile_misses"] <= out["buckets_possible"] == 4
    assert all(int(b) <= 8 for b in out["bucket_histogram"])
    json.loads(json.dumps(out))


def test_main_fails_without_a_tpu():
    """bench.py is one process that demands the chip: on this CPU host
    main() must exit non-zero before it measures anything (no CPU
    number is ever written under a device metric's name)."""
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert "needs a TPU" in str(exc.value)


def test_unknown_device_kind_is_an_error():
    assert bench._peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s on file"):
        bench._peak_flops("TPU v99")
