"""Plain reference of the factorizations the benchmark checks, and its control.

Independent of the program under test: nothing here imports ``repro``.  It is
a right-looking blocked algorithm written out in ``jax.numpy``:

- the diagonal blocks are factored and inverted by unblocked loops of
  rank-1 updates, which are elementwise and so exact f32 on every platform;
- the off-diagonal panels and the trailing updates go through ``matmul``,
  the one place the precision is chosen.

``precision="highest"`` is f32 (on a TPU, ``Precision.HIGHEST``, six bf16
passes).  ``precision="high"`` is the control: the three-pass bf16 product
(``hi*hi + hi*lo + lo*hi`` with f32 accumulation) that ``Precision.HIGH``
computes on a TPU, written out so that it means the same on every platform.

The blocked updates run over the whole matrix with masks, so every step has
the same shapes and the loop is one compiled ``fori_loop``: at n = 16384 and
512-wide blocks a Cholesky costs 32 full ``(n, b) @ (b, n)`` products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "high")


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def matmul(a, b, precision: str):
    """``a @ b`` in f32 (``highest``) or in three bf16 passes (``high``)."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bl) + dot(al, bh) + dot(ah, bh)


# -- unblocked kernels of the diagonal blocks (elementwise, exact f32) --------
def _chol_unblocked(a):
    n = a.shape[0]
    idx = jnp.arange(n)

    def step(k, a):
        d = jnp.sqrt(a[k, k])
        col = jnp.where(idx > k, a[:, k] / d, jnp.where(idx == k, d, 0.0))
        below = idx > k
        a = a - jnp.where(below[:, None] & below[None, :], col[:, None] * col[None, :], 0.0)
        return a.at[:, k].set(col)

    return jnp.tril(lax.fori_loop(0, n, step, a))


def _lu_unblocked(a):
    """Pivot-free Doolittle LU, packed: unit L below the diagonal, U on and above."""
    n = a.shape[0]
    idx = jnp.arange(n)

    def step(k, a):
        below = idx > k
        l = jnp.where(below, a[:, k] / a[k, k], 0.0)
        u = jnp.where(below, a[k, :], 0.0)
        a = a - l[:, None] * u[None, :]
        return a.at[:, k].set(jnp.where(below, l, a[:, k]))

    return lax.fori_loop(0, n, step, a)


def _inv_lower(l, unit: bool):
    """Inverse of a lower-triangular block by forward substitution on I."""
    n = l.shape[0]
    idx = jnp.arange(n)

    def step(k, x):
        row = x[k, :] if unit else x[k, :] / l[k, k]
        x = x.at[k, :].set(row)
        return x - jnp.where(idx > k, l[:, k], 0.0)[:, None] * row[None, :]

    return lax.fori_loop(0, n, step, jnp.eye(n, dtype=l.dtype))


def _inv_upper(u):
    """Inverse of an upper-triangular block by backward substitution on I."""
    n = u.shape[0]
    idx = jnp.arange(n)

    def step(j, x):
        k = n - 1 - j
        row = x[k, :] / u[k, k]
        x = x.at[k, :].set(row)
        return x - jnp.where(idx < k, u[:, k], 0.0)[:, None] * row[None, :]

    return lax.fori_loop(0, n, step, jnp.eye(n, dtype=u.dtype))


# -- blocked factorizations ----------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block", "precision"))
def cholesky(a, *, block: int, precision: str = "highest"):
    """Lower Cholesky factor of SPD ``a``."""
    n = a.shape[0]
    rows = jnp.arange(n)

    def step(k, a):
        k0 = k * block
        l11 = _chol_unblocked(lax.dynamic_slice(a, (k0, k0), (block, block)))
        col = lax.dynamic_slice(a, (0, k0), (n, block))
        panel = jnp.where(
            (rows >= k0 + block)[:, None], matmul(col, _inv_lower(l11, False).T, precision), 0.0
        )
        a = a - matmul(panel, panel.T, precision)
        a = lax.dynamic_update_slice(a, panel, (0, k0))
        return lax.dynamic_update_slice(a, l11, (k0, k0))

    return jnp.tril(lax.fori_loop(0, n // block, step, a))


def _lu_blocked(a, block, precision):
    n = a.shape[0]
    idx = jnp.arange(n)

    def step(k, a):
        k0 = k * block
        lu11 = _lu_unblocked(lax.dynamic_slice(a, (k0, k0), (block, block)))
        l_inv = _inv_lower(jnp.tril(lu11, -1) + jnp.eye(block, dtype=a.dtype), True)
        u_inv = _inv_upper(jnp.triu(lu11))
        trail = idx >= k0 + block
        lcol = jnp.where(
            trail[:, None],
            matmul(lax.dynamic_slice(a, (0, k0), (n, block)), u_inv, precision), 0.0,
        )
        urow = jnp.where(
            trail[None, :],
            matmul(l_inv, lax.dynamic_slice(a, (k0, 0), (block, n)), precision), 0.0,
        )
        a = a - matmul(lcol, urow, precision)
        keep_col = lax.dynamic_slice(a, (0, k0), (n, block))
        a = lax.dynamic_update_slice(a, jnp.where(trail[:, None], lcol, keep_col), (0, k0))
        keep_row = lax.dynamic_slice(a, (k0, 0), (block, n))
        a = lax.dynamic_update_slice(a, jnp.where(trail[None, :], urow, keep_row), (k0, 0))
        return lax.dynamic_update_slice(a, lu11, (k0, k0))

    return lax.fori_loop(0, n // block, step, a)


def _solve_packed(lu, b, block, precision):
    """``x`` with ``L U x = b`` for a packed pivot-free factor ``lu``."""
    n = lu.shape[0]
    p = n // block
    idx = jnp.arange(n)

    def forward(k, y):
        k0 = k * block
        l11 = jnp.tril(lax.dynamic_slice(lu, (k0, k0), (block, block)), -1)
        yk = matmul(_inv_lower(l11 + jnp.eye(block, dtype=lu.dtype), True),
                    lax.dynamic_slice(y, (k0, 0), (block, y.shape[1])), precision)
        lcol = jnp.where((idx >= k0 + block)[:, None],
                         lax.dynamic_slice(lu, (0, k0), (n, block)), 0.0)
        y = y - matmul(lcol, yk, precision)
        return lax.dynamic_update_slice(y, yk, (k0, 0))

    def backward(j, x):
        k0 = (p - 1 - j) * block
        u11 = jnp.triu(lax.dynamic_slice(lu, (k0, k0), (block, block)))
        xk = matmul(_inv_upper(u11), lax.dynamic_slice(x, (k0, 0), (block, x.shape[1])),
                    precision)
        ucol = jnp.where((idx < k0)[:, None], lax.dynamic_slice(lu, (0, k0), (n, block)), 0.0)
        x = x - matmul(ucol, xk, precision)
        return lax.dynamic_update_slice(x, xk, (k0, 0))

    y = lax.fori_loop(0, p, forward, b)
    return lax.fori_loop(0, p, backward, y)


@functools.partial(jax.jit, static_argnames=("block", "precision"))
def lu_solve(a, b, *, block: int, precision: str = "highest"):
    """``x`` with ``a @ x == b`` by pivot-free blocked LU; ``b`` a vector or matrix."""
    b2 = b[:, None] if b.ndim == 1 else b
    x = _solve_packed(_lu_blocked(a, block, precision), b2, block, precision)
    return x[:, 0] if b.ndim == 1 else x


def solve(op: str, inputs, *, block: int, precision: str = "highest"):
    """The reference answer of one request: ``op`` is ``cholesky`` or ``lu_solve``."""
    if op == "cholesky":
        return cholesky(inputs[0], block=block, precision=precision)
    if op == "lu_solve":
        return lu_solve(inputs[0], inputs[1], block=block, precision=precision)
    raise ValueError(f"no reference for {op!r}")


@functools.partial(jax.jit, static_argnames=("op", "block", "precision"))
def solve_many(op: str, stacked, *, block: int, precision: str = "highest"):
    """``solve`` over inputs stacked along a leading axis."""
    return jax.vmap(lambda *xs: solve(op, xs, block=block, precision=precision))(*stacked)


@jax.jit
def gap(x, ref):
    """``max|x - ref| / (eps n max|ref|)``: the answer's distance from the
    reference in f32 rounding units, scaled by the order ``n`` as HPL scales
    its residual."""
    n = ref.shape[0]
    eps = jnp.finfo(jnp.float32).eps
    return jnp.max(jnp.abs(x - ref)) / (eps * n * jnp.max(jnp.abs(ref)))
