"""Inputs made on the device from the seed, in one jitted call per pool.

The matrices follow the repository's ``spd_matrix`` / ``dd_matrix`` (copied
here so the yardstick does not move when the program's helpers change):

- ``spd``: ``G G^T / n + 2 I``, symmetric positive definite, eigenvalues in
  about [2, 6];
- ``dd``: strictly column-diagonally-dominant, so LU needs no pivoting
  (HPL-MxP's construction for a pivot-free factorization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key(seed: int):
    """A PRNG key from any whole number: its low 31 bits seed the key and the
    rest is folded in, so seeds past 2**31 stay distinct."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0xFFFFFFFF
    )


def np_rng(seed: int, *path: int):
    """The host-side generator of schedules and samples for ``seed``."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), *path])


def fold(k, *path):
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


def _spd(k, n):
    g = jax.random.normal(k, (n, n), jnp.float32)
    a = jnp.matmul(g, g.T, precision=HIGHEST) / n + 2.0 * jnp.eye(n, dtype=jnp.float32)
    return (a + a.T) / 2


def _dd(k, n):
    k1, k2 = jax.random.split(k)
    a = jax.random.normal(k1, (n, n), jnp.float32)
    a = a / (jnp.sum(jnp.abs(a), axis=0, keepdims=True) * 1.5)
    diag = 1.0 + jax.random.uniform(k2, (n,), jnp.float32)
    return jnp.where(jnp.eye(n, dtype=bool), diag[None, :], a)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def pool(k, kind: str, count: int, shape: tuple):
    """``count`` inputs of one kind, stacked: ``spd`` / ``dd`` matrices of
    order ``shape[0]``, or ``normal`` right-hand sides of ``shape``."""
    keys = jax.random.split(k, count)
    if kind == "spd":
        return jax.vmap(lambda kk: _spd(kk, shape[0]))(keys)
    if kind == "dd":
        return jax.vmap(lambda kk: _dd(kk, shape[0]))(keys)
    if kind == "normal":
        return jax.vmap(lambda kk: jax.random.normal(kk, shape, jnp.float32))(keys)
    raise ValueError(f"unknown input kind {kind!r}")


def unstack(stacked):
    """The members of a stacked pool as separate device arrays."""
    return [stacked[i] for i in range(stacked.shape[0])]
