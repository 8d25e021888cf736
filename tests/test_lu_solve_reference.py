"""The pivot-free LU solve on Pallas tile kernels (g2p, interpret mode on the
CPU) against the benchmark's plain reference (``bench/reference.py``: blocked
LU and substitutions in ``jax.numpy``, f32 products at ``HIGHEST``), on
seeded diagonally dominant systems.

The smallest shapes that reach every path: n = 256 in 64-wide tiles (a
4 x 4 grid: every LU kernel, both substitution chains), a vector
right-hand side (``(64, 1)`` tiles, read by BlockSpec on the chip) and a
2-column one.
"""

import pytest

from bench import inputs, reference
from repro.linalg import run_lu_solve

N, TILE = 256, 64

# ``reference.gap``: max|x - x_ref| / (eps n max|x_ref|).  Over five seeds
# each, the program read at most 0.0231 (vector) and 0.0193 (2 columns);
# the reference computed in three bf16 passes (one precision below the
# f32 the program states) read at least 0.219 and 0.261.  0.07 leaves the
# program three times its largest reading and fails that control by 3x.
LIMIT = 0.07


def _system(seed, nrhs):
    k = inputs.key(seed)
    a = inputs.pool(inputs.fold(k, 1), "dd", 1, (N,))[0]
    shape = (N,) if nrhs == 1 else (N, nrhs)
    return a, inputs.pool(inputs.fold(k, 2), "normal", 1, shape)[0]


@pytest.mark.parametrize("nrhs", [1, 2])
def test_g2p_lu_solve_matches_the_reference(nrhs):
    a, b = _system(2**31 + 11, nrhs)
    p = N // TILE
    x = run_lu_solve(a, b, graph="g2p", partitions=((p, p),), b_partitions=((p, 1),))
    ref = reference.lu_solve(a, b, block=TILE)
    assert x.shape == b.shape
    assert float(reference.gap(x, ref)) <= LIMIT
    # the check can tell the stated precision from the one below it
    high = reference.lu_solve(a, b, block=TILE, precision="high")
    assert float(reference.gap(high, ref)) > LIMIT
