"""Application + technical layers for LU, triangular solve, and the
end-to-end ``lu_solve`` drain (DESIGN.md §4/§6).

Mirrors ``cholesky.py``: ``utp_getrf`` / ``utp_solve`` / ``utp_lu_solve``
are the technical-layer subroutines (create one root task, submit it);
``run_lu`` / ``run_solve`` / ``run_lu_solve`` / ``run_inv`` are whole
application programs — define data + partitions, call the subroutine,
drain.  They run unmodified under every task-flow graph g1–g4 with zero
changes to executor code: the dispatcher only ever sees Operations.

Conventions (pivot-free Doolittle, see ``linalg/ops.py``):

    run_lu(a)                -> (L, U) with L unit-lower, U upper, L@U == a
    run_solve(a, b)          -> x with tril(a, unit) @ x == b
    run_solve(a, b, lower=False)              -> x with x @ triu(a) == b
    run_solve(a, b, lower=False, side="left") -> x with triu(a) @ x == b
    run_lu_solve(a, b)       -> x with a @ x == b  (factor+solve, ONE drain)
    run_inv(a)               -> inv(a)             (lu_solve against I)

``run_solve`` reads only the relevant triangle of ``a`` (the leaves mask
the other triangle), so a packed L\\U factor from ``run_lu`` can be passed
straight back in for forward/backward substitution.  ``run_lu_solve``
composes all of that as ONE dispatcher drain: LU panel tasks, L-solve
tasks, and U-solve tasks are versioned into a single task DAG and compiled
into a single WaveProgram (the composed LUSOLVE operation, DESIGN.md §4).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import Dispatcher, GData, GTask
from ..core.data import from_grid
from ..core.tracing import span
from ..errors import NumericalError
from .ops import GETRF, LUSOLVE, TRSML, TRSMU, TRSMUL


def check_finite_result(name: str, *arrays: jnp.ndarray) -> None:
    """Raise ``NumericalError`` if any result array is non-finite.

    The pivot-free expansions have no singular-pivot detection (the paper's
    fixed task-flow shape), so a zero pivot silently propagates inf/NaN
    through the trailing updates; ``check_finite=True`` on the run_* entry
    points turns that into a typed error instead of serving garbage
    (DESIGN.md §10).  Opt-in: the check forces materialization (de-grids a
    resident result), which the hot replay paths must not pay by default.
    """
    for a in arrays:
        if a is not None and not bool(jnp.isfinite(a).all()):
            raise NumericalError(
                f"{name}: non-finite values in result (singular pivot or "
                f"overflow; input not factorizable without pivoting?)"
            )


def utp_getrf(dispatcher: Dispatcher, A: GData) -> GTask:
    task = GTask(GETRF, None, [A.root_view()])
    dispatcher.submit_task(task)
    return task


@jax.jit
def _unpack_lu(packed: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    l = jnp.tril(packed, -1) + jnp.eye(packed.shape[0], dtype=packed.dtype)
    return l, jnp.triu(packed)


@jax.jit
def _unpack_lu_grid(grid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # de-grid + unpack in ONE compiled program: a drained root is still
    # grid-resident, and unpacking it unjitted costs three full-matrix
    # passes on the hot repeated-drain path (benchmarks time run_lu whole)
    return _unpack_lu(from_grid(grid))


def _unpack(A: GData) -> Tuple[jnp.ndarray, jnp.ndarray]:
    with span("utp.degrid"):
        if A.in_grid_epoch:
            return _unpack_lu_grid(A.grid)
        return _unpack_lu(A.value)


def utp_solve(
    dispatcher: Dispatcher,
    A: GData,
    B: GData,
    lower: bool = True,
    side: Optional[str] = None,
) -> GTask:
    """Submit one triangular-solve root task (technical layer).

    ``side`` defaults to the algebra's native orientation per triangle:
    "left" for lower (TRSML, forward substitution) and "right" for upper
    (TRSMU).  ``lower=False, side="left"`` selects TRSMUL — the left-upper
    backward substitution that closes ``A x = b`` end-to-end.
    """
    if side is None:
        side = "left" if lower else "right"
    if lower:
        if side != "left":
            raise ValueError("lower solves are left-sided (TRSML) only")
        op = TRSML
    else:
        if side not in ("left", "right"):
            raise ValueError(f"side must be 'left' or 'right', got {side!r}")
        op = TRSMUL if side == "left" else TRSMU
    task = GTask(op, None, [A.root_view(), B.root_view()])
    dispatcher.submit_task(task)
    return task


def utp_lu_solve(dispatcher: Dispatcher, A: GData, B: GData) -> GTask:
    """Submit ONE composed factor+solve root task (LUSOLVE, DESIGN.md §4).

    A single root keeps the whole expansion in one scope: the dispatcher
    versions LU panel tasks, forward-substitution tasks, and backward-
    substitution tasks into one task DAG and compiles one WaveProgram for
    the entire pipeline (instead of three barrier-separated drains).
    """
    task = GTask(LUSOLVE, None, [A.root_view(), B.root_view()])
    dispatcher.submit_task(task)
    return task


def run_lu(
    a: jnp.ndarray,
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    mesh=None,
    check_finite: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pivot-free blocked LU of ``a``; returns (L, U) unpacked.

    ``a`` must admit LU without pivoting (e.g. diagonally dominant or
    already factored-friendly); the task-flow expansion itself has no
    singular-pivot detection (the paper's fixed shape), but
    ``check_finite=True`` validates the drained factor and raises
    ``NumericalError`` instead of returning inf/NaN (DESIGN.md §10).
    """
    d = Dispatcher(graph=graph, mesh=mesh)
    A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=jnp.asarray(a))
    utp_getrf(d, A)
    d.run()
    if check_finite:
        check_finite_result("run_lu", A.value)
    return _unpack(A)


def run_lu_many(
    mats: Sequence[jnp.ndarray],
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    mesh=None,
) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Pivot-free blocked LU of several matrices in ONE dispatcher drain.

    The multi-root drain (ROADMAP item): every factorization is submitted
    as its own root task, the scheduler interleaves the independent task
    DAGs, and the dependency-exact fusion pass merges their same-signature
    groups into shared batched launches — one compiled program, one
    dispatch, for the whole set (DESIGN.md §2).  Stacking is deliberately
    OFF here: this is the per-root *segment fusion* form (the matrices may
    even have different shapes), and the measured baseline the stacked
    ``run_lu_batched`` is compared against (DESIGN.md §7).
    """
    d = Dispatcher(graph=graph, mesh=mesh, stack_roots=False)
    roots = []
    for a in mats:
        A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=jnp.asarray(a))
        utp_getrf(d, A)
        roots.append(A)
    d.run()
    return [_unpack(A) for A in roots]


def run_lu_batched(
    mats: Sequence[jnp.ndarray],
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    mesh=None,
) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Pivot-free blocked LU of N same-geometry matrices as ONE *stacked*
    batched drain (DESIGN.md §7).

    All matrices must share shape/dtype; the dispatcher detects the
    homogeneous root stream, stacks the roots along a new leading batch
    dimension padded to a pow2 bucket, and expands/compiles the task graph
    ONCE — launch count and compiled-program count are flat in N (any N
    hits one of O(log N) bucket programs), unlike ``run_lu_many`` whose
    fused groups still carry one gather/scatter segment per root.
    """
    d = Dispatcher(graph=graph, mesh=mesh)
    roots = []
    for a in mats:
        A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=jnp.asarray(a))
        utp_getrf(d, A)
        roots.append(A)
    d.run()
    return [_unpack(A) for A in roots]


def run_solve(
    a: jnp.ndarray,
    b: jnp.ndarray,
    lower: bool = True,
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    b_partitions: Tuple[Tuple[int, int], ...] = None,
    mesh=None,
    side: Optional[str] = None,
    check_finite: bool = False,
) -> jnp.ndarray:
    """Blocked triangular solve as a task workload.

    ``lower=True``: x = inv(tril(a, unit-diagonal)) @ b (forward subst.).
    ``lower=False``: x = b @ inv(triu(a)) (backward substitution from the
    right), or x = inv(triu(a)) @ b with ``side="left"`` (the left-upper
    TRSMUL orientation).  ``b_partitions`` defaults to ``partitions``; give
    it explicitly for non-square block counts (b's row grid must match a's
    for left-sided solves, its column grid for the right-sided one).
    """
    d = Dispatcher(graph=graph, mesh=mesh)
    A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=jnp.asarray(a))
    B = GData(
        b.shape,
        partitions=partitions if b_partitions is None else b_partitions,
        dtype=b.dtype,
        value=jnp.asarray(b),
    )
    utp_solve(d, A, B, lower=lower, side=side)
    d.run()
    if check_finite:
        check_finite_result("run_solve", B.value)
    return B.value


def run_lu_solve(
    a: jnp.ndarray,
    b: jnp.ndarray,
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    b_partitions: Tuple[Tuple[int, int], ...] = None,
    mesh=None,
    check_finite: bool = False,
) -> jnp.ndarray:
    """Solve ``a @ x == b`` by pivot-free LU — factor AND solve in ONE drain.

    The whole pipeline (LU panel tasks, forward-substitution tasks,
    backward-substitution tasks) is submitted as one composed LUSOLVE root,
    so it is versioned into one task DAG, compiled into one WaveProgram,
    and replayed via the drain memo on structurally repeated calls — the
    same single-drain/zero-recompile behaviour ``run_lu`` has, now for the
    full solve (DESIGN.md §4).  Matches ``jax.scipy.linalg.lu_solve`` on
    inputs where partial pivoting selects P == I (e.g. column-diagonally-
    dominant ``a``); like ``run_lu``, the expansion has no singular-pivot
    detection, but ``check_finite=True`` raises ``NumericalError`` on a
    non-finite solution instead of returning it (DESIGN.md §10).

    ``b`` may be a matrix ``(n, m)`` or a vector ``(n,)``; ``b_partitions``
    defaults to ``partitions`` with the column counts collapsed to 1 for a
    vector right-hand side.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if b.shape[0] != a.shape[0]:
        raise ValueError(f"shape mismatch: a {a.shape} vs b {b.shape}")
    vec = b.ndim == 1
    b2 = b[:, None] if vec else b
    if b_partitions is None:
        b_partitions = tuple(
            (pr, 1 if vec else pc) for pr, pc in partitions
        )
    d = Dispatcher(graph=graph, mesh=mesh)
    A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=a)
    B = GData(b2.shape, partitions=b_partitions, dtype=b2.dtype, value=b2)
    utp_lu_solve(d, A, B)
    d.run()
    x = B.value
    if check_finite:
        check_finite_result("run_lu_solve", x)
    return x[:, 0] if vec else x


def run_lu_solve_batched(
    mats: Sequence[jnp.ndarray],
    rhss: Sequence[jnp.ndarray],
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    b_partitions: Tuple[Tuple[int, int], ...] = None,
    mesh=None,
) -> List[jnp.ndarray]:
    """Solve N same-geometry systems ``a_i @ x_i == b_i`` in ONE stacked
    drain (DESIGN.md §7): N composed LUSOLVE roots stack into a single
    batched WaveProgram — the serving hot path ``BatchServer`` drains per
    tick.  Geometry rules follow ``run_lu_solve`` (vector or matrix b)."""
    if len(mats) != len(rhss):
        raise ValueError(f"{len(mats)} matrices vs {len(rhss)} right-hand sides")
    d = Dispatcher(graph=graph, mesh=mesh)
    outs = []
    for a, b in zip(mats, rhss):
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        if b.shape[0] != a.shape[0]:
            raise ValueError(f"shape mismatch: a {a.shape} vs b {b.shape}")
        vec = b.ndim == 1
        b2 = b[:, None] if vec else b
        bp = b_partitions
        if bp is None:
            bp = tuple((pr, 1 if vec else pc) for pr, pc in partitions)
        A = GData(a.shape, partitions=partitions, dtype=a.dtype, value=a)
        B = GData(b2.shape, partitions=bp, dtype=b2.dtype, value=b2)
        utp_lu_solve(d, A, B)
        outs.append((B, vec))
    d.run()
    return [B.value[:, 0] if vec else B.value for B, vec in outs]


def run_inv(
    a: jnp.ndarray,
    graph: str = "g2",
    partitions: Tuple[Tuple[int, int], ...] = ((4, 4),),
    mesh=None,
) -> jnp.ndarray:
    """Matrix inverse via LU: ``run_lu_solve(a, I)`` — a second application
    program over the same composed pipeline (A X = I), showing the family
    is closed: no new operations, no executor changes."""
    a = jnp.asarray(a)
    eye = jnp.eye(a.shape[0], dtype=a.dtype)
    return run_lu_solve(a, eye, graph=graph, partitions=partitions, mesh=mesh)
