"""Operations and bytes each kernel's algorithm needs, from shapes.

Counts use real rows and real nonzeros, never the padded ones, so a
kernel's roofline share reads the same work whatever implements it.
"""
from __future__ import annotations


def encode_bytes(nnz: int, rows: int, k: int, b: int) -> int:
    """b-bit encode with packing: 4 bytes in per real nonzero, the
    packed codes out (ceil(k·b/8) bytes a row)."""
    return 4 * nnz + rows * ((k * b + 7) // 8)


def logits_train(rows: int, steps: int, k: int, b: int, c: int):
    """(operations, bytes) of the packed logits forward plus its dW
    backward over ``rows`` rows in ``steps`` steps.

    Operations: 2·k·C a row each way (the one-hot expansion has exactly
    k ones a row).  Bytes: the packed codes read by both kernels
    (k·b/8 a row each), and per step the (k, 2^b, C) float32 table read
    by the forward and its gradient written by the backward."""
    ops = 4 * rows * k * c
    table = k * (1 << b) * c * 4
    codes = rows * ((k * b + 7) // 8)
    return ops, 2 * codes + 2 * table * steps


def model_flops_per_row(k: int, c: int) -> int:
    """Model operations of one trained row of the linear model over the
    one-hot expansion: 2·k·C forward, 2·k·C for dW."""
    return 4 * k * c


def roofline_seconds(ops: float, nbytes: float, peak_ops: float,
                     peak_bytes_per_s: float) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(ops / peak_ops, nbytes / peak_bytes_per_s)
