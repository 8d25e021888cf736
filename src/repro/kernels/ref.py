"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests).

Conventions match the blocked left-looking Cholesky (paper Fig. 2b):
    potrf(a)      -> lower Cholesky factor L of a
    trsm(l, b)    -> b @ inv(l)^T         (right, lower, transposed)
    syrk(a, c)    -> c - a @ a^T
    gemm(a, b, c) -> c - a @ b^T
and the blocked right-looking pivot-free LU (DESIGN.md §6):
    getrf(a)        -> packed L\\U factors (L unit-lower implicit, U upper)
    trsml(l, b)     -> inv(tril(l, unit)) @ b   (left, lower, unit-diagonal)
    trsmu(u, b)     -> b @ inv(triu(u))         (right, upper, non-unit)
    trsmul(u, b)    -> inv(triu(u)) @ b         (left, upper, non-unit)
    gemmnn(a, b, c) -> c - a @ b
    lu_solve(a, b)  -> (packed L\\U of a, x with a @ x == b)
All oracles compute in float32 (matmuls at ``Precision.HIGHEST``, so a TPU
does not round f32 operands to bf16 passes) and cast back to the input
dtype.  The
triangular-solve oracles read only their own triangle (plus U's diagonal),
so packed L\\U blocks can be passed without masking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(_f32(a), _f32(b), precision=jax.lax.Precision.HIGHEST)


def potrf(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.linalg.cholesky(_f32(a)).astype(a.dtype)


def trsm(l: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    x = solve_triangular(_f32(l), _f32(b).T, lower=True)
    return x.T.astype(b.dtype)


def syrk(a: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    return (_f32(c) - _mm(a, _f32(a).T)).astype(c.dtype)


def gemm(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    return (_f32(c) - _mm(a, _f32(b).T)).astype(c.dtype)


def getrf(a: jnp.ndarray) -> jnp.ndarray:
    """Pivot-free right-looking LU; returns L\\U packed into one matrix.

    Delegates to the shared pure-jnp tile body (``_getrf_tile`` uses no
    Pallas primitives): pivot-free LU has exactly one defined recurrence,
    so a re-implementation here could only diverge from it.  Independent
    coverage comes from ``jax.scipy.linalg.lu`` comparisons in test_lu.py.
    """
    from .tile_linalg import _getrf_tile

    return _getrf_tile(_f32(a)).astype(a.dtype)


def trsml(l: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    x = solve_triangular(_f32(l), _f32(b), lower=True, unit_diagonal=True)
    return x.astype(b.dtype)


def trsmu(u: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # x @ u = b  <=>  u^T x^T = b^T (solve_triangular reads triu(u) only)
    x = solve_triangular(_f32(u), _f32(b).T, lower=False, trans="T")
    return x.T.astype(b.dtype)


def trsmul(u: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    # left-upper backward substitution (solve_triangular reads triu(u) only)
    x = solve_triangular(_f32(u), _f32(b), lower=False)
    return x.astype(b.dtype)


def gemmnn(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    return (_f32(c) - _mm(a, b)).astype(c.dtype)


def lu_solve(a: jnp.ndarray, b: jnp.ndarray):
    """Whole lu_solve pipeline on one block: factor then two substitutions.

    Returns ``(packed, x)`` — one updated array per READWRITE argument of
    the composed LUSOLVE operation (a is replaced by its packed L\\U factor,
    b by the solution of ``a @ x == b``)."""
    packed = getrf(a)
    return packed, trsmul(packed, trsml(packed, b))


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _mm(a, b).astype(a.dtype)


def flash_attention(
    q: jnp.ndarray,  # (B, Hq, S, D)
    k: jnp.ndarray,  # (B, Hkv, S, D)
    v: jnp.ndarray,  # (B, Hkv, S, D)
    causal: bool = True,
    window: int = 0,  # 0 = global; >0 = local sliding window
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference attention with GQA head-group broadcasting."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    kq = jnp.repeat(_f32(k), g, axis=1)
    vq = jnp.repeat(_f32(v), g, axis=1)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", _f32(q) * scale, kq, precision=jax.lax.Precision.HIGHEST
    )
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window > 0:
        mask &= ki > qi - window
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p, vq, precision=jax.lax.Precision.HIGHEST
    ).astype(q.dtype)
