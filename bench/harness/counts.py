"""Operations and bytes that the algorithm needs, as functions of a
cell's shapes: what a right-looking blocked Cholesky and a refinement
sweep must do, not what one implementation of them happens to do.

Flops count a multiply and an add as two. Bytes count each float32
element (4 bytes) read or written once per panel or call.
"""
from __future__ import annotations

F32 = 4


def _trailing(n: int, leaf: int):
    """Rows below the diagonal block of each panel that has a trailing
    update: m_p = n - (p + 1) * leaf for p = 0 .. n/leaf - 2."""
    assert n % leaf == 0, (n, leaf)
    return [n - (p + 1) * leaf for p in range(n // leaf - 1)]


def panel_flops(n: int, leaf: int) -> int:
    """The fused panel update over a whole factorization: per panel the
    TRSM L21 = A21 L11^-T (m * leaf^2 flops, a triangular solve) and the
    lower half of the trailing SYRK A22 -= L21 L21^T (m * (m + 1) * leaf
    flops). Sums to about n^3 / 3."""
    return sum(m * leaf * leaf + m * (m + 1) * leaf
               for m in _trailing(n, leaf))


def panel_bytes(n: int, leaf: int) -> int:
    """Per panel: read and write the lower trailing triangle
    (m (m + 1) / 2 elements each way), read A21 and write L21 (m * leaf
    each)."""
    return sum(F32 * (m * (m + 1) + 2 * m * leaf)
               for m in _trailing(n, leaf))


def residual_flops(rows: int, n: int, k: int) -> int:
    """r = b - A x for an (rows, n) A and k columns: 2 rows n k."""
    return 2 * rows * n * k


def residual_bytes(rows: int, n: int, k: int) -> int:
    """Read A (rows x n), x (n x k) and b (rows x k); write r (rows x k)."""
    return F32 * (rows * n + n * k + 2 * rows * k)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float):
    """The least time the chip could take over the time taken, in
    percent, and the bound that sets it (``"compute"`` or
    ``"memory"``)."""
    t_compute = flops / peak_flops
    t_memory = nbytes / peak_bytes
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
