"""The FLOPs and bytes functions against numbers worked by hand."""

import pytest

from benchmark import modelcfg, roofline


def test_mistral_train_flops_per_token():
    cfg = modelcfg.load("mistral-7b-v0.3")
    # per layer: qkv 4096*(4096+2*1024), o 4096*4096, swiglu 3*4096*14336
    layer = 25_165_824 + 16_777_216 + 176_160_768
    assert roofline.matmul_params(cfg) == 2 * layer + 4096 * 32768 \
        == 570_425_344
    # causal attention: 6 * L * heads*head_dim * (S+1) = 6*2*4096*2049
    assert roofline.train_flops_per_token(cfg, 2048) \
        == 6 * 570_425_344 + 100_712_448 == 3_523_264_512


def test_flash_forward_and_backward():
    # b4, 32 heads over 8 kv heads, s2048, d128, bf16
    flops, nbytes = roofline.flash_fwd(4, 32, 8, 2048, 128)
    assert flops == 4 * 4 * 32 * 2048 * 2048 * 128 // 2 == 137_438_953_472
    assert nbytes == 4 * 2048 * 128 * 80 * 2 + 4 * 32 * 2048 * 4 \
        == 168_820_736
    bflops, bbytes = roofline.flash_bwd(4, 32, 8, 2048, 128)
    assert bflops == 5 * flops // 2
    assert bbytes == 4 * 2048 * 128 * 160 * 2 + 4 * 32 * 2048 * 4


def test_peaks_and_bound():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("source")
    t, bound = roofline.least_seconds(*roofline.flash_fwd(4, 32, 8, 2048, 128),
                                      v5e)
    assert bound == "compute" and t == pytest.approx(137_438_953_472 / 197e12)
    t, bound = roofline.least_seconds(1e9, 819e6, v5e)
    assert bound == "memory" and t == pytest.approx(1e-3)
