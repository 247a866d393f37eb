"""The analytic FLOPs and the kernels' byte/FLOP functions at worked sizes."""
import json
import os

import pytest
from conftest import BENCH

import flops
import peaks


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_matmul_parameters():
    # 24 x 12 x 2048^2 + 2048 x 50000 = 1.310 B; 12 x 12 x 768^2 + 768 x 50000
    assert flops.matmul_params(cfg("galactica-1.3b")) == 24 * 12 * 2048**2 + 2048 * 50000
    assert flops.matmul_params(cfg("galactica-125m")) == 12 * 12 * 768**2 + 768 * 50000
    assert round(flops.matmul_params(cfg("galactica-1.3b")) / 1e9, 3) == 1.310
    assert round(flops.matmul_params(cfg("galactica-125m")) / 1e6, 1) == 123.3


def test_token_flops():
    big, small = cfg("galactica-1.3b"), cfg("galactica-125m")
    assert flops.train_token_flops(big, 2048) == pytest.approx(8.47e9, rel=2e-3)
    assert flops.train_token_flops(small, 2048) == pytest.approx(0.85e9, rel=5e-3)
    assert flops.serve_token_flops(big, 0) == pytest.approx(2.62e9, rel=1e-3)
    # attention over a live context of 512: 4 x 24 x 2048 x 512 more
    assert flops.serve_token_flops(big, 512) - flops.serve_token_flops(big, 0) == 4 * 24 * 2048 * 512


def test_flash_decode_call_is_memory_bound():
    big = cfg("galactica-1.3b")
    work = flops.flash_decode_call(big, [300] * 16)
    # 16 slots x 300 positions x 32 heads x 64 x 4 bytes, K and V
    assert work["bytes"] == 2 * 16 * 300 * 2048 * 4 + 2 * 16 * 2048 * 4
    assert work["flops"] == 4.0 * 16 * 300 * 2048
    least, bound = flops.roofline_least_seconds(work, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(work["bytes"] / 819e9)


def test_flash_attention_calls_are_compute_bound():
    small = cfg("galactica-125m")
    one = 2.0 * (8 * 12 * 2048 * 2048 / 2) * 64
    tensor = 8 * 2048 * 768 * 2
    for kernel, tensors in (("fwd", 4), ("bwd_dq", 5), ("bwd_dkv", 6)):
        work = flops.flash_attention_call(small, 8, 2048, kernel)
        assert work == {"flops": 2 * one, "bytes": tensors * tensor}
        least, bound = flops.roofline_least_seconds(work, peaks.peaks_for("TPU v5 lite"))
        assert bound == "compute" and least == pytest.approx(2 * one / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
