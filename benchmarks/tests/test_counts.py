"""FLOPs and bytes functions against hand counts at the published
shapes, and the peaks table."""

import pytest
import tiny  # noqa: F401
from lobench import counts, loader, peaks


def _cp(name):
    bench = loader.benchmark()
    return loader.config(loader.config_path(bench, name))[0][
        "class_parameters"
    ]


def test_bert_base_train_flops_per_token():
    cp = _cp("bert-base-uncased")
    # one block: qkv 3*768^2 + out 768^2 + mlp 2*768*3072 = 7,077,888
    assert counts.block_matmul_params(cp) == 7_077_888
    # forward a layer: 2*7,077,888 + 4*512*768 = 15,728,640
    fwd = 12 * 15_728_640 + 2 * (768 * 768 + 768 * 2) / 512
    got = counts.encoder_train_flops_per_token(cp, 512)
    assert got == pytest.approx(3 * fwd)
    assert got == pytest.approx(566.2e6, rel=2e-3)


def test_gpt2_xl_decode_counts():
    cp = _cp("gpt2-xl")
    # one block: 4*1600^2 + 2*1600*6400 = 30,720,000
    assert counts.block_matmul_params(cp) == 30_720_000
    at_256 = counts.decoder_forward_flops_per_token(cp, 256)
    assert at_256 == pytest.approx(
        48 * (2 * 30_720_000 + 4 * 256 * 1600) + 2 * 1600 * 50257
    )
    # weights a step reads: 48 blocks (matrices + 9,600 + 6,400 biases
    # and norms... by hand: 30,720,000 + 4800+1600+6400+1600 + 6400)
    block = 30_720_000 + (4800 + 1600 + 6400 + 1600) + 4 * 1600
    head = 1600 * 50257 + 50257 + 2 * 1600
    assert counts.decoder_weight_bytes(cp) == 4 * (48 * block + head)
    assert counts.decoder_weight_bytes(cp) == pytest.approx(6.22e9, rel=5e-3)
    # K and V of 8 slots x 300 keys: 2*48*2400*1600*4
    assert counts.decoder_kv_bytes(cp, 2400) == 2 * 48 * 2400 * 1600 * 4


def test_flash_counts_and_bound():
    cp = _cp("bert-base-uncased")
    flops = counts.flash_train_flops(cp, 32, 512)
    # six 512 x 512 x 64 matmuls a head, 32 x 12 heads
    assert flops == 6 * 2 * 512 * 512 * 64 * 32 * 12
    nbytes = counts.flash_train_bytes(cp, 32, 512)
    assert nbytes == 12 * 32 * 512 * 768 * 2
    least, bound = counts.roofline_seconds(
        flops, nbytes, peaks.PEAKS["TPU v5 lite"]
    )
    assert bound == "compute"
    assert least == pytest.approx(flops / 197e12)
    assert counts.roofline_seconds(1.0, 1e9, peaks.PEAKS["TPU v5 lite"])[1] \
        == "memory"


def test_unknown_device_kind_is_refused():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        peaks.peaks_for("cpu")
