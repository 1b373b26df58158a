"""bench/counts.py and bench/peaks.py on shapes worked by hand."""
import pytest

import bench_small  # noqa: F401
from bench import counts, peaks


def test_encode_bytes_by_hand():
    # 1,000 real nonzeros in 3 rows at k=500, b=8: 4,000 B of ids in,
    # 3 x 500 B of packed codes out
    assert counts.encode_bytes(1000, 3, 500, 8) == 4000 + 1500
    # k·b/8 rounds up per row: k=3, b=3 is 9 bits, 2 bytes
    assert counts.encode_bytes(0, 2, 3, 3) == 4


def test_logits_train_by_hand():
    # 2 steps of 1,024 rows, k=256, b=8, one output: 4·n·k·C operations;
    # codes 256 B a row read twice; a 256 x 256 x 1 float32 table
    # (256 KiB) read and its gradient written each step
    ops, nbytes = counts.logits_train(2048, 2, 256, 8, 1)
    assert ops == 4 * 2048 * 256
    assert nbytes == 2 * 2048 * 256 + 2 * (256 * 256 * 4) * 2


def test_model_flops_and_roofline():
    assert counts.model_flops_per_row(256, 1) == 1024
    assert counts.model_flops_per_row(500, 3) == 6000
    # bound by bytes: 819 MB at 819 GB/s is a millisecond
    assert counts.roofline_seconds(1e9, 819e6, 197e12, 819e9) == \
        pytest.approx(1e-3)
    # bound by operations
    assert counts.roofline_seconds(197e12, 1.0, 197e12, 819e9) == \
        pytest.approx(1.0)


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
