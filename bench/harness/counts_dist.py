"""Operations and bytes a Cholesky factorization needs, whatever
implements it and however it is laid out over chips: the n^3 / 3 flops
of the trailing updates (a multiply and an add count as two) and the
lower triangle of float32 read and written once.

The distributed sweep's share of its roofline compares a chip's share
of this work, the whole over the number of chips, with the time the
busiest chip spends in it.
"""
from __future__ import annotations

F32 = 4


def update_flops(n: int) -> float:
    """The trailing updates of an n x n Cholesky: n^3 / 3."""
    return n ** 3 / 3.0


def update_bytes(n: int) -> float:
    """The lower triangle (n (n + 1) / 2 float32 elements) read once and
    written once."""
    return float(F32 * n * (n + 1))
