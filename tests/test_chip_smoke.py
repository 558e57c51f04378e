"""chip_smoke.py's phases at tiny widths on the CPU, through the same
REST calls the chip run makes — so a broken request, a renamed metadata
field or a shape bug is found here and not on chip time — plus the
contract that ``main()`` refuses to run without a TPU."""

import json
import resource

import jax
import pytest

import chip_smoke
from learningorchestra_tpu.jobs.leases import DeviceLeaser

TINY_BERT = dict(
    class_parameters=dict(
        vocab_size=64, hidden_dim=32, num_layers=2, num_heads=2,
        max_len=16,
    ),
    vocab=64, seq=16, batch=8, epochs=2, rows=16, predict_rows=8,
    serve_calls=2,
    # Off-TPU the attention layer takes the jnp path: no Mosaic call to
    # count, and the reference is the same arithmetic.
    min_kernel_calls=0, logit_tol=1e-4,
)
TINY_DECODE = dict(
    class_parameters=dict(
        hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64, max_len=32,
    ),
    vocab=32, seq=16, batch=8, rows=16, prompt=4, new_tokens=4,
    concurrent=3, logit_tol=1e-3,
)


@pytest.fixture
def file_size_limit():
    """A process file-size limit (``ulimit -f``) below the size of the
    tiny models' train artifacts (~0.4 MB with the Adam state), as the
    machine that checks chip_smoke.py has one below BERT-base's 1.3 GB:
    a write past it fails with EFBIG."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (128 << 10, hard))
    yield 128 << 10
    resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))


def test_every_phase_over_rest_on_virtual_devices(tmp_path, capsys,
                                                  file_size_limit):
    """``run`` as ``main`` calls it, on the 8 virtual CPU devices (so
    the multi-chip branch runs too), leasing them so the lease and
    placement checks apply here as on the chip."""
    leaser = DeviceLeaser([f"cpu:{d.id}" for d in jax.devices()])
    device = chip_smoke.device_report()
    chip_smoke.run(
        device, tmp_path, bert=TINY_BERT, decode=TINY_DECODE,
        ring=dict(t=64, d=8), leaser=leaser,
    )
    lines = [
        json.loads(line)
        for line in capsys.readouterr().out.strip().splitlines()
    ]
    out = {line["phase"]: line for line in lines}
    assert list(out) == ["boot", "bert", "decode", "multichip"]
    # The limit bit: the fit's artifact did not fit one file.
    parts = tmp_path / "volumes" / "binaries" / ".bert_fit.parts"
    assert len(list(parts.iterdir())) > 1
    assert all(
        p.stat().st_size <= file_size_limit for p in tmp_path.rglob("*")
        if p.is_file()
    )

    bert = out["bert"]
    assert len(bert["losses"]) == 2
    assert bert["paramDevices"] == bert["leasedDevices"]
    assert bert["predictRows"] == 8

    assert out["decode"]["requests"] == 4
    assert out["decode"]["newTokensEach"] == 4

    multi, n = out["multichip"], device["count"]
    for name in ("bert_dist", "bert_dist_tp"):
        assert multi[name]["meshDevices"] == n
        assert len(set(multi[name]["paramDevices"])) == n
    a, b = multi["concurrentFits"]["devices"]
    assert a != b
    assert set(multi["ringFlash"]) == {"full", "causal"}


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    # The device report comes first; no result line follows it.
    assert json.loads(lines[0])["platform"] == "cpu"
    assert not any('"ok"' in line for line in lines)
    assert "needs a TPU" in captured.err
