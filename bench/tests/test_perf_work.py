"""FLOP and byte counts against hand counts, and the peak table."""
import pytest

from bench import work


def test_kernel_counts_by_hand():
    # 512^3 voxels: read 4 B + write 4 B; one divide and three differences
    v = 512 ** 3
    assert work.lorenzo_quant(v) == (4.0 * v, 8.0 * v)
    assert work.huffman_pack(10, 7) == (0.0, 47.0)
    assert work.huffman_probe(10, 7) == (0.0, 47.0)
    # one group per voxel, C=9: 2*9*9 + 2*9*9 multiply-adds x2, 4*9 for BN+ReLU
    assert work.enhancer_forward(1, 9) == (2 * 81 + 2 * 81 + 36, 8.0)
    f, b = work.enhancer_training(steps=2, pixels_per_step=10, passes_pixels=5)
    assert f == 3 * 20 * 360 + 5 * 360 and b == 3 * 20 * 8 + 5 * 8


def test_least_seconds_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert work.least_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, peak) == pytest.approx(1.0)
    assert work.add((1.0, 2.0), (3.0, 4.0)) == (4.0, 6.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
