"""The operation counts of ``bench/programs/ssl_mlp.py`` against counts made by hand."""

import json

import pytest

from bench import cells, peaks
from bench.programs import ssl_mlp

SUM = json.loads((cells.BENCH / "configs" / "ssl-bt-sum-d8192.json").read_text())
OFF = json.loads((cells.BENCH / "configs" / "ssl-bt-off-d8192.json").read_text())


# examples/ssl_pretrain.py's own model, the benchmark's first shape: 3072 -> 4096 -> 4096, two
# projector layers of 8192
PRETRAIN = dict(SUM, input_dim=3072, backbone_widths=[4096, 4096], projector_layers=2)


def test_parameters_are_130_0_million():
    weights = 3072 * 4096 + 4096 * 4096 + 4096 * 8192 + 8192 * 8192
    assert weights == 130_023_424
    assert ssl_mlp.param_count(PRETRAIN) == weights + 4096 + 4096 + 8192 + 8192
    assert round(ssl_mlp.param_count(PRETRAIN) / 1e6, 1) == 130.0


def test_encoder_at_256_is_0_39_tflop():
    # 6 n P per view, less the first layer's input gradient, both views
    p = 130_023_424
    hand = 2 * (6 * 256 * p - 2 * 256 * 3072 * 4096)
    assert ssl_mlp.encoder_flops(PRETRAIN, 256) == hand
    assert hand == pytest.approx(0.3865e12, rel=1e-3)
    # with the first layer's input gradient counted too, 0.40
    assert hand + 2 * 2 * 256 * 3072 * 4096 == pytest.approx(0.40e12, rel=3e-3)


def test_configurations_hold_155_2_million_parameters():
    # one 2048-wide backbone layer, Barlow Twins' projector 2048 -> 8192 -> 8192 -> 8192
    weights = 2048 * 2048 + 2048 * 8192 + 8192 * 8192 + 8192 * 8192
    assert weights == 155_189_248
    for cfg in (SUM, OFF):
        assert ssl_mlp.param_count(cfg) == weights + 2048 + 3 * 8192
    assert round(ssl_mlp.param_count(SUM) / 1e6, 1) == 155.2


def test_configurations_encoder_at_256_is_0_47_tflop():
    p = 155_189_248
    hand = 2 * (6 * 256 * p - 2 * 256 * 2048 * 2048)
    assert ssl_mlp.encoder_flops(SUM, 256) == hand
    assert hand == pytest.approx(0.4724e12, rel=1e-3)


def test_r_off_is_six_n_d_squared():
    assert ssl_mlp.regularizer_flops(OFF, 256) == 6 * 256 * 8192 * 8192
    assert ssl_mlp.regularizer_flops(OFF, 256) == pytest.approx(103.1e9, rel=1e-3)


def test_grouped_r_sum():
    n, d, b = 256, 8192, 128
    nb, nf = d // b, b // 2 + 1
    dft = 2 * n * d * (b + 2)          # one view's block DFT as one product
    spectra = 8 * n * nb * nb * nf     # complex nb x nb outer products per frequency
    hand = 2 * dft + spectra + 2 * dft + 2 * spectra
    assert ssl_mlp.regularizer_flops(SUM, n) == hand
    q1 = dict(SUM, q=1)
    assert ssl_mlp.regularizer_flops(q1, n) == hand + 6 * nb * nb * nf * b
    # VICReg: each view against itself, one DFT per call
    vic = dict(SUM, style="vic", q=1)
    assert ssl_mlp.regularizer_flops(vic, n) == 2 * (2 * dft + 3 * spectra + 6 * nb * nb * nf * b)
    assert ssl_mlp.regularizer_flops(dict(OFF, style="vic"), n) == 2 * 6 * n * d * d


def test_step_is_encoder_plus_regularizer():
    for cfg in (SUM, OFF):
        for n in (256, 2048):
            assert ssl_mlp.step_flops(cfg, n) == ssl_mlp.encoder_flops(cfg, n) + ssl_mlp.regularizer_flops(cfg, n)
    assert ssl_mlp.step_flops(SUM, 2048) == pytest.approx(3.810e12, rel=1e-3)


def test_peaks_know_the_v5e_and_nothing_else():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v4")
