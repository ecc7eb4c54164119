"""FLOP and byte functions against hand counts (bench/lib/flops.py)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.lib import flops  # noqa: E402
from bench.lib.peaks import chip_peaks  # noqa: E402

MAMBA2 = {"d_model": 768, "n_layer": 24, "d_state": 128, "expand": 2,
          "headdim": 64, "ngroups": 1, "chunk_size": 256,
          "vocab_size": 50280}


def test_mnist_mlp():
    # 784*200+200 + 200*200+200 + 200*10+10
    assert flops.mlp_params([784, 200, 200, 10]) == 199_210
    assert flops.mlp_flops_per_sample([784, 200, 200, 10]) == 6 * 199_210


def test_weighted_agg_cost():
    f, b = flops.weighted_agg_cost(100, 199_210, 4)
    assert f == 2 * 100 * 199_210
    assert b == 100 * 199_210 * 4 + 100 * 4 + 199_210 * 4
    f, b = flops.weighted_agg_cost(4, 10, 2)
    assert (f, b) == (80, 4 * 10 * 2 + 16 + 40)


def test_mamba2_per_token():
    # in_proj 768 x (2*1536 + 2*128 + 24), out_proj 1536 x 768, 24 layers,
    # tied head 768 x 50280
    per_layer = 768 * 3352 + 1536 * 768
    assert flops.mamba2_matmul_params(MAMBA2) == 24 * per_layer + 768 * 50280
    # SSD: 2QN + H (2QP + 4NP) with Q=256, N=128, P=64, H=24
    ssd = 2 * 256 * 128 + 24 * (2 * 256 * 64 + 4 * 128 * 64)
    assert flops.mamba2_ssd_flops_per_token(MAMBA2) == ssd
    total = 6 * (24 * per_layer + 768 * 50280) + 3 * 24 * ssd
    assert flops.mamba2_flops_per_token(MAMBA2) == total
    assert 0.85e9 < total < 0.95e9


def test_peaks_known_and_unknown():
    assert chip_peaks("TPU v5 lite")["flops_bf16"] == 197e12
    try:
        chip_peaks("TPU v9000")
    except ValueError:
        pass
    else:
        raise AssertionError("an unknown device kind must raise")
