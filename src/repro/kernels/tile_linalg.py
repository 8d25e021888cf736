"""Pallas TPU tile kernels for blocked dense linear algebra.

These are the leaves of the UTP task hierarchy (the paper's cuBLAS wrapper
analog).  Every kernel is *batched*: it takes a stack of tiles ``(n, b, b)``
and maps the batch over the Pallas grid, so a whole wave of independent
same-shaped tasks becomes ONE kernel launch (DESIGN.md §2: wave batching).

TPU adaptation notes:
  - tiles live in VMEM via explicit ``BlockSpec``s; ``b`` should be a
    multiple of 128 so the MXU sees aligned matmuls (tests sweep smaller
    shapes in interpret mode where alignment is not enforced);
  - POTRF/GETRF are column-recurrences (O(b) steps of rank-1 work on the
    VPU); the panel solves (TRSM/TRSML/TRSMU/TRSMUL) are row recurrences
    run only on 128-row diagonal strips, each strip first taking the
    already-solved rows as one MXU matmul.  All of them are only ever
    applied to the O(p) diagonal/panel tiles while the O(p^3) trailing
    updates (SYRK/GEMM) are single MXU matmuls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else interpret


def _tile_spec(b: int):
    return pl.BlockSpec((1, b, b), lambda i: (i, 0, 0))


def _stack_spec(shape):
    """BlockSpec for one (possibly non-square) tile of an (n, br, bc) stack."""
    return pl.BlockSpec((1,) + tuple(shape[1:]), lambda i: (i, 0, 0))


# --------------------------------------------------------------------------
# Tile bodies — pure (b, b) math shared by the batched per-tile kernels and
# the fused grid kernels below.
#
# The panel bodies are written for Mosaic: every value is 2-D, and a row or
# column at the loop index is selected with a broadcasted-iota mask and a
# reduction, never by dynamic slicing or indexed update of a traced value
# (Mosaic cannot lower ``dynamic_slice`` on values).  Each step of
# ``_potrf_tile`` and ``_getrf_tile`` is a masked rank-1 update of the whole
# tile on the VPU.  The panel solves are blocked: the right-hand side is cut
# into strips of ``_panel_strip(nb)`` rows by static slices; each strip
# first takes everything already solved as one MXU dot, then runs a row
# recurrence with only the strip's diagonal block of the triangular
# factor.  The right-side solves (TRSM, TRSMU) run on the transposes, so no
# step reduces across lanes.  Off-diagonal blocks of a packed L\U tile are
# pure L or pure U, so only the diagonal block needs the recurrence's mask.
# --------------------------------------------------------------------------
def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _col(m, j):
    """Column ``j`` of 2-D ``m`` as an (r, 1) array."""
    return jnp.sum(jnp.where(_iota(m.shape, 1) == j, m, 0.0), axis=1, keepdims=True)


def _row(m, i):
    """Row ``i`` of 2-D ``m`` as a (1, c) array."""
    return jnp.sum(jnp.where(_iota(m.shape, 0) == i, m, 0.0), axis=0, keepdims=True)


def _transpose_col(v):
    """(n, 1) -> (1, n) through a diagonal mask (no vector transpose)."""
    n = v.shape[0]
    diag = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(diag, v, 0.0), axis=0, keepdims=True)


def _potrf_tile(a: jnp.ndarray) -> jnp.ndarray:
    """Lower Cholesky factor of one tile (reads the lower triangle only).

    Right-looking: step j takes column j of the updated tile as L[:, j] and
    subtracts its outer product from the trailing block.
    """
    a = a.astype(jnp.float32)
    b = a.shape[-1]
    rows, cols = _iota((b, b), 0), _iota((b, b), 1)
    rv = _iota((b, 1), 0)

    def body(j, m):
        c = _col(m, j)
        d = jnp.sqrt(_row(c, j))  # (1, 1) pivot
        l = jnp.where(rv > j, c / d, jnp.where(rv == j, d, 0.0))
        trail = (rows > j) & (cols > j)
        m = jnp.where(trail, m - l * _transpose_col(l), m)
        return jnp.where(cols == j, l, m)

    m = lax.fori_loop(0, b, body, a)
    return jnp.where(rows >= cols, m, 0.0)


# f32 operands through the MXU at full precision (Mosaic's default for a
# dot is bf16 passes)
_HIGHEST = lax.Precision.HIGHEST


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=_HIGHEST)


# the panel solves whose tile bodies run in ``_panel_strip`` strips
PANEL_SOLVES = ("trsm", "trsml", "trsmu", "trsmul")


def _panel_strip(nb: int) -> int:
    """Width of the diagonal strips a panel solve of a triangular tile of
    order ``nb`` runs its recurrence on: the lane width where the tile holds
    several whole lane-wide strips, else the whole tile (one strip, no
    dots)."""
    return 128 if nb % 128 == 0 and nb > 128 else nb


def _diag(m: jnp.ndarray) -> jnp.ndarray:
    """Diagonal of square 2-D ``m`` as an (n, 1) array."""
    n = m.shape[0]
    return jnp.sum(
        jnp.where(_iota((n, n), 0) == _iota((n, n), 1), m, 0.0), axis=1, keepdims=True
    )


def _lower_rec(L: jnp.ndarray, B: jnp.ndarray, unit: bool) -> jnp.ndarray:
    """X = inv(L) @ B by right-looking row recurrence, L lower.

    Step i subtracts L[k, i] * (X[i] / L[i, i]) from every row k > i; rows
    <= i see a zero multiplier, so the mask is on L's (nb, 1) column, not on
    the strip.  Row i itself is divided by L[i, i] once, after the loop,
    which gives the quotient the step used.  Only L's strict lower triangle
    (and its diagonal unless ``unit``) is read.
    """
    nb = L.shape[-1]
    rv = _iota((nb, 1), 0)

    def body(i, X):
        li = _col(L, i)  # (nb, 1)
        x = _row(X, i) if unit else _row(X, i) / _row(li, i)
        return X - jnp.where(rv > i, li, 0.0) * x

    X = lax.fori_loop(0, nb, body, B)
    return X if unit else X / _diag(L)


def _lower_solve(L: jnp.ndarray, B: jnp.ndarray, unit: bool = False) -> jnp.ndarray:
    """X = inv(L) @ B, L lower, in row strips of ``_panel_strip`` rows.

    Row strip I: X[I] = B[I] - L[I, :I0] @ X[:I0], solved against L[I, I].
    """
    nb = L.shape[-1]
    w = _panel_strip(nb)
    xs = []
    for i0 in range(0, nb, w):
        I = slice(i0, i0 + w)
        rhs = B[I]
        if xs:
            rhs = rhs - _dot(L[I, :i0], jnp.concatenate(xs, axis=0))
        xs.append(_lower_rec(L[I, I], rhs, unit))
    return jnp.concatenate(xs, axis=0)


def _trsm_tile(L: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """X = B @ inv(L)^T, L lower non-unit: X^T = inv(L) @ B^T, solved by
    rows (no lane reduction over the right-hand side)."""
    return _lower_solve(L.astype(jnp.float32), B.astype(jnp.float32).T).T


def _getrf_tile(a: jnp.ndarray) -> jnp.ndarray:
    """Pivot-free right-looking LU of one tile; L\\U packed (unit L implicit).

    Column recurrence on the VPU: scale column k below the pivot, then one
    masked rank-1 update of the trailing submatrix — O(b) steps, mirroring
    ``_potrf_tile``.
    """
    a = a.astype(jnp.float32)
    b = a.shape[-1]
    rows, cols = _iota((b, b), 0), _iota((b, b), 1)
    rv, cv = _iota((b, 1), 0), _iota((1, b), 1)

    def body(k, m):
        c = _col(m, k)
        l = jnp.where(rv > k, c / _row(c, k), 0.0)
        u = jnp.where(cv > k, _row(m, k), 0.0)
        m = jnp.where((cols == k) & (rows > k), l, m)
        return m - l * u

    return lax.fori_loop(0, b, body, a)


def _trsml_tile(L: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """X = inv(L) @ B with L unit-lower (stored diagonal/upper ignored).

    Only L's strict lower triangle is read, so packed L\\U blocks pass
    unmasked.
    """
    return _lower_solve(L.astype(jnp.float32), B.astype(jnp.float32), unit=True)


def _trsmu_tile(U: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """X = B @ inv(U) with U upper non-unit (stored lower junk ignored):
    X^T = inv(U^T) @ B^T, U^T lower.  Only U's upper triangle is read."""
    return _lower_solve(U.astype(jnp.float32).T, B.astype(jnp.float32).T).T


def _trsmul_rec(U: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """X = inv(U) @ B by bottom-up row recurrence, U upper non-unit.

    Step i subtracts U[k, i] * X[i] / U[i, i] from every row k < i, the
    mask on U's column as in ``_lower_rec``; rows are divided by the
    diagonal after the loop.  Only U's upper triangle is read.
    """
    nb = U.shape[-1]
    rv = _iota((nb, 1), 0)

    def body(t, X):
        i = nb - 1 - t
        ui = _col(U, i)  # (nb, 1)
        x = _row(X, i) / _row(ui, i)
        return X - jnp.where(rv < i, ui, 0.0) * x

    return lax.fori_loop(0, nb, body, B) / _diag(U)


def _trsmul_tile(U: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """X = inv(U) @ B with U upper non-unit (stored lower junk ignored).

    Row strips bottom-up: X[I] = B[I] - U[I, I1:] @ X[I1:], solved against
    U[I, I].  Only U's upper triangle is read, so packed L\\U blocks pass
    unmasked.
    """
    U = U.astype(jnp.float32)
    B = B.astype(jnp.float32)
    nb = U.shape[-1]
    w = _panel_strip(nb)
    xs = []  # solved strips, top first
    for i1 in range(nb, 0, -w):
        I = slice(i1 - w, i1)
        rhs = B[I]
        if xs:
            rhs = rhs - _dot(U[I, i1:], jnp.concatenate(xs, axis=0))
        xs.insert(0, _trsmul_rec(U[I, I], rhs))
    return jnp.concatenate(xs, axis=0)


def _gemmnn_tile(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    return c.astype(jnp.float32) - jnp.dot(
        a, b, preferred_element_type=jnp.float32, precision=_HIGHEST
    )


def _syrk_tile(a: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    return c.astype(jnp.float32) - jnp.dot(
        a, a.T, preferred_element_type=jnp.float32, precision=_HIGHEST
    )


def _gemm_tile(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    return c.astype(jnp.float32) - jnp.dot(
        a, b.T, preferred_element_type=jnp.float32, precision=_HIGHEST
    )


# --------------------------------------------------------------------------
# POTRF: batched lower Cholesky of (n, b, b) tiles
# --------------------------------------------------------------------------
def _potrf_kernel(a_ref, l_ref):
    L = _potrf_tile(a_ref[...][0])
    l_ref[...] = L[None].astype(l_ref.dtype)


def batched_potrf(a: jnp.ndarray, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    n, b, _ = a.shape
    return pl.pallas_call(
        _potrf_kernel,
        grid=(n,),
        in_specs=[_tile_spec(b)],
        out_specs=_tile_spec(b),
        out_shape=jax.ShapeDtypeStruct((n, b, b), a.dtype),
        name="potrf",
        interpret=_resolve(interpret),
    )(a)


# --------------------------------------------------------------------------
# TRSM: batched X = B @ inv(L)^T  (right, lower-triangular, transposed)
# --------------------------------------------------------------------------
def _trsm_kernel(l_ref, b_ref, x_ref):
    X = _trsm_tile(l_ref[...][0], b_ref[...][0])
    x_ref[...] = X[None].astype(x_ref.dtype)


def batched_trsm(
    l: jnp.ndarray, b: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n, nb, _ = l.shape
    return pl.pallas_call(
        _trsm_kernel,
        grid=(n,),
        in_specs=[_tile_spec(nb), _tile_spec(nb)],
        out_specs=_tile_spec(nb),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        name="trsm",
        interpret=_resolve(interpret),
    )(l, b)


# --------------------------------------------------------------------------
# SYRK: batched C - A @ A^T   /   GEMM: batched C - A @ B^T  (MXU matmuls)
# --------------------------------------------------------------------------
def _syrk_kernel(a_ref, c_ref, o_ref):
    upd = _syrk_tile(a_ref[...][0], c_ref[...][0])
    o_ref[...] = upd[None].astype(o_ref.dtype)


def batched_syrk(
    a: jnp.ndarray, c: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n, b, _ = a.shape
    return pl.pallas_call(
        _syrk_kernel,
        grid=(n,),
        in_specs=[_tile_spec(b), _tile_spec(b)],
        out_specs=_tile_spec(b),
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        name="syrk",
        interpret=_resolve(interpret),
    )(a, c)


def _gemm_kernel(a_ref, b_ref, c_ref, o_ref):
    upd = _gemm_tile(a_ref[...][0], b_ref[...][0], c_ref[...][0])
    o_ref[...] = upd[None].astype(o_ref.dtype)


def batched_gemm(
    a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n, nb, _ = a.shape
    return pl.pallas_call(
        _gemm_kernel,
        grid=(n,),
        in_specs=[_tile_spec(nb), _tile_spec(nb), _tile_spec(nb)],
        out_specs=_tile_spec(nb),
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        name="gemm",
        interpret=_resolve(interpret),
    )(a, b, c)


# --------------------------------------------------------------------------
# GETRF: batched pivot-free LU  /  TRSML: batched inv(L) @ B (left, unit-
# lower)  /  TRSMU: batched B @ inv(U) (right, upper)  /  GEMMNN: batched
# C - A @ B — the LU operation family (DESIGN.md §6)
# --------------------------------------------------------------------------
def _getrf_kernel(a_ref, o_ref):
    o_ref[...] = _getrf_tile(a_ref[...][0])[None].astype(o_ref.dtype)


def batched_getrf(a: jnp.ndarray, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    n, b, _ = a.shape
    return pl.pallas_call(
        _getrf_kernel,
        grid=(n,),
        in_specs=[_tile_spec(b)],
        out_specs=_tile_spec(b),
        out_shape=jax.ShapeDtypeStruct((n, b, b), a.dtype),
        name="getrf",
        interpret=_resolve(interpret),
    )(a)


def _trsml_kernel(l_ref, b_ref, x_ref):
    X = _trsml_tile(l_ref[...][0], b_ref[...][0])
    x_ref[...] = X[None].astype(x_ref.dtype)


def batched_trsml(
    l: jnp.ndarray, b: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n, nb, _ = l.shape
    # b tiles may be non-square (e.g. a blocked vector right-hand side)
    return pl.pallas_call(
        _trsml_kernel,
        grid=(n,),
        in_specs=[_tile_spec(nb), _stack_spec(b.shape)],
        out_specs=_stack_spec(b.shape),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        name="trsml",
        interpret=_resolve(interpret),
    )(l, b)


def _trsmu_kernel(u_ref, b_ref, x_ref):
    X = _trsmu_tile(u_ref[...][0], b_ref[...][0])
    x_ref[...] = X[None].astype(x_ref.dtype)


def batched_trsmu(
    u: jnp.ndarray, b: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n, nb, _ = u.shape
    return pl.pallas_call(
        _trsmu_kernel,
        grid=(n,),
        in_specs=[_tile_spec(nb), _stack_spec(b.shape)],
        out_specs=_stack_spec(b.shape),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        name="trsmu",
        interpret=_resolve(interpret),
    )(u, b)


def _trsmul_kernel(u_ref, b_ref, x_ref):
    X = _trsmul_tile(u_ref[...][0], b_ref[...][0])
    x_ref[...] = X[None].astype(x_ref.dtype)


def batched_trsmul(
    u: jnp.ndarray, b: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n, nb, _ = u.shape
    return pl.pallas_call(
        _trsmul_kernel,
        grid=(n,),
        in_specs=[_tile_spec(nb), _stack_spec(b.shape)],
        out_specs=_stack_spec(b.shape),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        name="trsmul",
        interpret=_resolve(interpret),
    )(u, b)


def _gemmnn_kernel(a_ref, b_ref, c_ref, o_ref):
    upd = _gemmnn_tile(a_ref[...][0], b_ref[...][0], c_ref[...][0])
    o_ref[...] = upd[None].astype(o_ref.dtype)


def batched_gemmnn(
    a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray, *, interpret: Optional[bool] = None
) -> jnp.ndarray:
    n = a.shape[0]
    return pl.pallas_call(
        _gemmnn_kernel,
        grid=(n,),
        in_specs=[_stack_spec(a.shape), _stack_spec(b.shape), _stack_spec(c.shape)],
        out_specs=_stack_spec(c.shape),
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        name="gemmnn",
        interpret=_resolve(interpret),
    )(a, b, c)


# --------------------------------------------------------------------------
# Fused grid kernels (DESIGN.md §2, grid-resident epoch).
#
# Gather -> compute -> scatter in ONE kernel over the resident
# ``(nr, nc, br, bc)`` grid: per-task block coordinates arrive as
# scalar-prefetched int32 arrays (``(n, 2)`` flattened), and the output
# aliases the written arg's grid so the scatter is in place — no gathered
# tile stacks ever materialize in HBM.
#
# Each distinct grid is ONE operand, left in HBM (``pltpu.HBM``); arguments
# that share a grid (every argument of a Cholesky group is a block of the
# one matrix) read their tiles from that operand by DMA.  Passing one buffer
# twice with one of the two aliased to the output forces XLA to copy the
# whole grid before the call, since the aliased operand is overwritten
# while the other is still read.  Reads are double-buffered by hand: step
# i+1's tiles are started before step i's are waited on, and a tile whose
# block coordinates equal the previous step's is not fetched again.  The
# output keeps its blocked spec, so the scatter's writeback stays
# pipelined.  Callers must pass exact (unpadded) group sizes: tasks in a
# group are independent, so no block a group reads is written by the same
# group, but duplicated trailing indices would re-read their own scatter
# for read-write operations.
# --------------------------------------------------------------------------
def _dma_readable(tile_shape, dtype, interpret: bool) -> bool:
    """Whether Mosaic can DMA one ``tile_shape`` tile out of a grid in HBM:
    it slices HBM only at whole (sublane, lane) tiles of the layout."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return interpret or (tile_shape[-2] % sublanes == 0 and tile_shape[-1] % 128 == 0)


def make_grid_fused(tile_fn, arity: int, write_arg: int, name: str):
    """Build a fused gather/compute/scatter entry point for ``tile_fn``.

    ``tile_fn(*tiles) -> tile`` is the pure per-tile body; ``write_arg`` is
    the argument whose grid receives the result (and whose blocks the output
    aliases); ``name`` is the kernel's name on the device (the operation's).
    Returns ``call(idxs, grids, arg_grid, *, interpret=None) -> new grid``:
    ``grids`` are the distinct grids and ``arg_grid[a]`` indexes the one
    argument ``a`` reads.  Each grid is one operand of the kernel; the
    written one is aliased to the output.

    ``call`` accepts either resident single-workload grids
    ``(nr, nc, br, bc)`` or *stacked* grids ``(B, nr, nc, br, bc)`` holding B
    structurally identical workloads (DESIGN.md §7): the stacked form runs
    the same kernel body under a leading batch grid dimension — grid
    ``(B, n)``, steps in ``(b, i)`` order, reads prefetched across lanes —
    with the per-lane block-index array shared by every lane, so a batch of
    B costs one launch and no extra index traffic.
    """

    def call(idxs, grids, arg_grid, *, interpret: Optional[bool] = None):
        from jax.experimental.pallas import tpu as pltpu

        interp = _resolve(interpret)
        assert len(idxs) == arity == len(arg_grid)
        assert sorted(set(arg_grid)) == list(range(len(grids)))
        wg = grids[arg_grid[write_arg]]
        stacked = wg.ndim == 5
        grid = (wg.shape[0], idxs[0].shape[0]) if stacked else (idxs[0].shape[0],)
        lead = (1,) * len(grid) + (1,)

        # block coordinates arrive flattened to (2n,) int32: a 2-D (n, 2) SMEM
        # array pads every row to 128 words, which overflows the 1 MiB SMEM at
        # group sizes of a few hundred
        def block_map(a):
            def imap(*at):
                r, i = at[len(grid) + a], at[len(grid) - 1]
                return at[: len(grid) - 1] + (r[2 * i], r[2 * i + 1], 0, 0)

            return imap

        # operands: each grid read by DMA once, in HBM; a grid whose tiles
        # Mosaic cannot slice there is read by BlockSpec, one operand per
        # argument (and then copied by XLA when it is also written)
        operands, in_specs, src, hbm = [], [], [], {}
        for a, k in enumerate(arg_grid):
            g = grids[k]
            if not _dma_readable(g.shape[-2:], g.dtype, interp):
                src.append(len(operands))
                in_specs.append(pl.BlockSpec(lead + g.shape[-2:], block_map(a)))
                operands.append(g)
                continue
            if k not in hbm:
                hbm[k] = len(operands)
                in_specs.append(pl.BlockSpec(memory_space=pltpu.HBM))
                operands.append(g)
            src.append(hbm[k])
        dma = [a for a in range(arity) if arg_grid[a] in hbm]

        def kernel(*refs):
            idx = refs[:arity]
            ops = refs[arity : arity + len(operands)]
            o_ref = refs[arity + len(operands)]
            bufs = dict(zip(dma, refs[arity + len(operands) + 1 : -2]))
            sem, cur = refs[-2:]  # DMA semaphores (2, arity); slot per arg
            # scalar bookkeeping in lax, not jnp operators: the kernel is
            # traced anew for every group, and each jnp operator traces a
            # wrapper of its own
            add, eq, sel, not_ = lax.add, lax.eq, lax.select, lax.bitwise_not
            i, steps = pl.program_id(len(grid) - 1), pl.num_programs(len(grid) - 1)
            last = eq(i, steps - 1)
            nxt = sel(last, 0, add(i, 1))  # step i+1, or step 0 of lane+1
            prv = lax.max(add(i, -1), 0)
            if stacked:
                lane, lanes = pl.program_id(0), pl.num_programs(0)
                first = eq(add(lane, i), 0)
                more = not_(lax.bitwise_and(last, eq(lane, lanes - 1)))
                nlane = sel(last, lax.min(add(lane, 1), lanes - 1), lane)
            else:
                lane = nlane = None
                first, more = eq(i, 0), not_(last)

            def copy(a, ln, at, slot):
                tile = ops[src[a]].at[at if ln is None else (ln,) + at]
                return pltpu.make_async_copy(tile, bufs[a].at[slot], sem.at[slot, a])

            def same(u, v):
                return lax.bitwise_and(eq(u[0], v[0]), eq(u[1], v[1]))

            # block coordinates of the previous, this and the next step
            rows = [lax.mul(t, 2) for t in (prv, i, nxt)]
            cols = [add(r, 1) for r in rows]
            pos = {a: [(idx[a][r], idx[a][c]) for r, c in zip(rows, cols)] for a in dma}

            @pl.when(first)
            def _():
                for a in dma:
                    copy(a, lane, pos[a][1], 0).start()
                    cur[a] = 0

            slot, at, fetched = {}, {}, {}
            for a in dma:
                p_, at[a], n_ = pos[a]
                # start step i+1's read into the other slot, unless the
                # tile is the one already held
                c = slot[a] = cur[a]
                keep = lax.bitwise_and(not_(last), same(at[a], n_))
                cur[a] = sel(keep, c, lax.sub(1, c))

                @pl.when(lax.bitwise_and(more, not_(keep)))
                def _():
                    copy(a, nlane, n_, lax.sub(1, c)).start()

                fetched[a] = lax.bitwise_or(eq(i, 0), not_(same(p_, at[a])))

            # then wait for step i's own reads
            for a, c in slot.items():

                @pl.when(fetched[a])
                def _():
                    copy(a, lane, at[a], c).wait()

            out = tile_fn(
                *(
                    bufs[a][slot[a]] if a in slot else ops[src[a]][(0,) * len(lead)]
                    for a in range(arity)
                )
            )
            o_ref[(0,) * len(lead)] = out.astype(o_ref.dtype)

        spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=arity,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(lead + wg.shape[-2:], block_map(write_arg)),
            scratch_shapes=[
                pltpu.VMEM((2,) + g.shape[-2:], g.dtype)
                for g in (grids[arg_grid[a]] for a in dma)
            ]
            + [pltpu.SemaphoreType.DMA((2, arity)), pltpu.SMEM((arity,), jnp.int32)],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=spec,
            out_shape=jax.ShapeDtypeStruct(wg.shape, wg.dtype),
            input_output_aliases={arity + src[write_arg]: 0},
            # steps carry the read buffers from one to the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid)
            ),
            name=name,
            interpret=interp,
        )(*(ix.reshape(-1) for ix in idxs), *operands)

    return call


grid_potrf = make_grid_fused(_potrf_tile, arity=1, write_arg=0, name="potrf")
grid_trsm = make_grid_fused(_trsm_tile, arity=2, write_arg=1, name="trsm")
grid_syrk = make_grid_fused(_syrk_tile, arity=2, write_arg=1, name="syrk")
grid_gemm = make_grid_fused(_gemm_tile, arity=3, write_arg=2, name="gemm")
grid_getrf = make_grid_fused(_getrf_tile, arity=1, write_arg=0, name="getrf")
grid_trsml = make_grid_fused(_trsml_tile, arity=2, write_arg=1, name="trsml")
grid_trsmu = make_grid_fused(_trsmu_tile, arity=2, write_arg=1, name="trsmu")
grid_trsmul = make_grid_fused(_trsmul_tile, arity=2, write_arg=1, name="trsmul")
grid_gemmnn = make_grid_fused(_gemmnn_tile, arity=3, write_arg=2, name="gemmnn")

# op name -> (fused call, write_arg); consumed by the WaveProgram compiler
# when the backend is 'pallas' and the group writes exactly that argument.
GRID_FUSED = {
    "potrf": (grid_potrf, 0),
    "trsm": (grid_trsm, 1),
    "syrk": (grid_syrk, 1),
    "gemm": (grid_gemm, 2),
    "getrf": (grid_getrf, 0),
    "trsml": (grid_trsml, 1),
    "trsmu": (grid_trsmu, 1),
    "trsmul": (grid_trsmul, 1),
    "gemmnn": (grid_gemmnn, 2),
}


# --------------------------------------------------------------------------
# General tiled matmul with K-revisiting and a VMEM fp32 accumulator —
# the canonical MXU pattern (used standalone and by benchmarks).
# --------------------------------------------------------------------------
def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...],
        b_ref[...],
        preferred_element_type=jnp.float32,
        precision=_HIGHEST if a_ref.dtype == jnp.float32 else None,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bn, bk)
    nk = k // bk
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=_resolve(interpret),
    )(a, b)
