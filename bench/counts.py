"""Operations and bytes the algorithms need, computed from their shapes.

These count the work of the trailing updates as the algorithm defines it,
not what a given kernel happens to compute, so a roofline share reads the
same work whatever implements it.  A posit word is 4 bytes.
"""
from __future__ import annotations

WORD = 4


def gemm(m: int, n: int, k: int) -> tuple[float, float]:
    """C (m x n) <- C - A (m x k) B (k x n): 2 m n k operations; A and B
    read once, C read and written."""
    return 2.0 * m * n * k, WORD * (m * k + k * n + 2.0 * m * n)


def _blocks(n: int, nb: int):
    """(rows below the panel, panel width) of each blocked step that has
    a trailing update."""
    for j in range(0, n, nb):
        w = min(nb, n - j)
        if j + w < n:
            yield n - j - w, w


def lu_updates(n: int, nb: int) -> list[tuple[float, float]]:
    """The right-looking LU's trailing GEMMs, one per block step."""
    return [gemm(m, m, w) for m, w in _blocks(n, nb)]


def least_seconds(updates, flops_per_s: float, bytes_per_s: float) -> float:
    """Least time the chip could take for ``updates`` [(ops, bytes)]: per
    update the larger of ops over peak rate and bytes over peak
    bandwidth, summed."""
    return sum(max(ops / flops_per_s, nbytes / bytes_per_s)
               for ops, nbytes in updates)
