"""Operations and bytes of the blocked factorizations, from their shapes alone.

A drain over a ``p x p`` grid of ``b x b`` f32 tiles runs one task per tile
operation.  ``tasks`` lists them as ``(kernel, count, flops, bytes)`` per
task:

- flops are the algorithm's count (a symmetric update counts half a
  product), so that the tasks of a drain sum to the textbook total:
  ``n^3 / 3`` for Cholesky and ``2 n^3 / 3 + 2 n^2 nrhs`` for the composed
  LU factor-and-solve;
- bytes are the tiles the task reads and the tile it writes, each once, at
  4 bytes an element: ``(arity + 1) b^2 4`` for square tiles.

``roofline_s`` is the least time the chip could take for a list of tasks:
for each task the larger of its flops at peak and its bytes at bandwidth.
"""

from __future__ import annotations

from typing import List, Tuple

F32 = 4

Task = Tuple[str, int, float, float]  # (kernel, count, flops each, bytes each)


def _tiles(*shapes) -> float:
    return float(sum(r * c for r, c in shapes) * F32)


def cholesky_tasks(n: int, b: int) -> List[Task]:
    """POTRF / TRSM / SYRK / GEMM of a right-looking tile Cholesky."""
    p = n // b
    sq = (b, b)
    return [
        ("potrf", p, b**3 / 3, _tiles(sq, sq)),
        ("trsm", p * (p - 1) // 2, float(b**3), _tiles(sq, sq, sq)),
        ("syrk", p * (p - 1) // 2, float(b**3), _tiles(sq, sq, sq)),
        ("gemm", (p - 2) * (p - 1) * p // 6, 2.0 * b**3, _tiles(sq, sq, sq, sq)),
    ]


def lu_solve_tasks(n: int, b: int, nrhs: int) -> List[Task]:
    """Pivot-free tile LU, then forward and backward substitution over a
    right-hand side cut into ``(b, nrhs)`` row blocks."""
    p = n // b
    sq, rhs = (b, b), (b, nrhs)
    pairs = p * (p - 1) // 2
    return [
        ("getrf", p, 2 * b**3 / 3, _tiles(sq, sq)),
        ("trsml", pairs, float(b**3), _tiles(sq, sq, sq)),
        ("trsmu", pairs, float(b**3), _tiles(sq, sq, sq)),
        ("gemmnn", (p - 1) * p * (2 * p - 1) // 6, 2.0 * b**3, _tiles(sq, sq, sq, sq)),
        # forward substitution: L_kk^-1 y_k, then y_i -= L_ik y_k
        ("trsml.rhs", p, float(b * b * nrhs), _tiles(sq, rhs, rhs)),
        ("gemmnn.rhs_forward", pairs, 2.0 * b * b * nrhs, _tiles(sq, rhs, rhs, rhs)),
        # backward substitution: U_kk^-1 x_k, then x_i -= U_ik x_k
        ("trsmul.rhs", p, float(b * b * nrhs), _tiles(sq, rhs, rhs)),
        ("gemmnn.rhs_backward", pairs, 2.0 * b * b * nrhs, _tiles(sq, rhs, rhs, rhs)),
    ]


def tasks(op: str, n: int, b: int, nrhs: int = 1) -> List[Task]:
    if op == "cholesky":
        return cholesky_tasks(n, b)
    if op == "lu_solve":
        return lu_solve_tasks(n, b, nrhs)
    raise ValueError(f"no task model for {op!r}")


def algorithmic_flops(op: str, n: int, nrhs: int = 1) -> float:
    """The textbook operation count of one solution."""
    if op == "cholesky":
        return n**3 / 3
    if op == "lu_solve":
        return 2 * n**3 / 3 + 2 * n**2 * nrhs
    raise ValueError(f"no operation count for {op!r}")


def total(task_list: List[Task]) -> Tuple[float, float]:
    """(flops, bytes) summed over a task list."""
    return (
        sum(c * f for _, c, f, _ in task_list),
        sum(c * by for _, c, _, by in task_list),
    )


def roofline_s(task_list: List[Task], peak_flops: float, bandwidth: float) -> Tuple[float, str]:
    """Least time for the tasks on a chip, and which bound dominates it
    (``"bytes"`` or ``"flops"``, by the larger of the two sums)."""
    t = sum(c * max(f / peak_flops, by / bandwidth) for _, c, f, by in task_list)
    fl, by = total(task_list)
    return t, ("bytes" if by / bandwidth >= fl / peak_flops else "flops")
