"""Blocked pivot-free LU + triangular solve as first-class task workloads.

The unified-interface claim (paper abstract, DESIGN.md §6): the SAME
dispatcher, executors, and task-flow graphs g1–g4 that run Cholesky must
run the LU family with zero changes to executor code.  Numerics are checked
against ``jax.scipy.linalg.lu`` / ``solve_triangular`` on strictly
column-diagonally-dominant inputs (where partial pivoting provably selects
P == I, making the pivoted library factors directly comparable), across
both leaf backends, with non-square block counts, and the repeated-drain
compile-cache behaviour is asserted via the PR-1 drain memo.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import (
    lu as scipy_lu,
    lu_factor,
    lu_solve as scipy_lu_solve,
    solve_triangular,
)

from repro.compat import make_mesh
from repro.core import Dispatcher, GData, OpRegistry, dd_matrix, utp_get_parameters
from repro.core.executors import clear_compile_cache
from repro.linalg import run_inv, run_lu, run_lu_solve, run_solve
from repro.linalg.lu import utp_getrf, utp_lu_solve


def _mesh_1d():
    return make_mesh((1, 1), ("data", "model"))


def _lu_ref(a):
    p, l, u = scipy_lu(np.asarray(a))
    np.testing.assert_array_equal(np.asarray(p), np.eye(a.shape[0]))
    return np.asarray(l), np.asarray(u)


# --------------------------------------------------------------------------
# run_lu vs jax.scipy.linalg.lu across every graph, both backends
# --------------------------------------------------------------------------
@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
@pytest.mark.parametrize("n,parts", [(32, ((2, 2),)), (64, ((4, 4),))])
def test_lu_single_level(graph, n, parts):
    a = dd_matrix(n, seed=n)
    L, U = run_lu(a, graph=graph, partitions=parts)
    l_ref, u_ref = _lu_ref(a)
    np.testing.assert_allclose(np.asarray(L), l_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(U), u_ref, atol=1e-5)


@pytest.mark.parametrize("graph", ["g3", "g4", "g3flat"])
def test_lu_distributed_graphs(graph):
    n = 64
    a = dd_matrix(n, seed=7)
    parts = ((2, 2), (2, 2)) if graph in ("g3", "g4") else ((4, 4),)
    L, U = run_lu(a, graph=graph, partitions=parts, mesh=_mesh_1d())
    l_ref, u_ref = _lu_ref(a)
    np.testing.assert_allclose(np.asarray(L), l_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(U), u_ref, atol=1e-5)


def test_lu_same_program_all_graphs_identical():
    """Portability: ONE run_lu program, any graph, same factors."""
    a = dd_matrix(32, seed=11)
    outs = {}
    for g in ("g1", "g2", "g2p"):
        L, U = run_lu(a, graph=g, partitions=((2, 2),))
        outs[g] = (np.asarray(L), np.asarray(U))
    for g, (L, U) in outs.items():
        np.testing.assert_allclose(L, outs["g1"][0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(U, outs["g1"][1], rtol=1e-5, atol=1e-5)


def test_lu_hierarchical_matches_flat():
    a = dd_matrix(64, seed=9)
    Lf, Uf = run_lu(a, graph="g2", partitions=((4, 4),))
    Lh, Uh = run_lu(a, graph="g3", partitions=((2, 2), (2, 2)), mesh=_mesh_1d())
    np.testing.assert_allclose(np.asarray(Lf), np.asarray(Lh), atol=1e-5)
    np.testing.assert_allclose(np.asarray(Uf), np.asarray(Uh), atol=1e-5)


# --------------------------------------------------------------------------
# run_solve vs solve_triangular, incl. non-square block counts
# --------------------------------------------------------------------------
@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
@pytest.mark.parametrize("bshape,bparts", [((64, 64), ((4, 4),)), ((64, 32), ((4, 2),))])
def test_solve_lower(graph, bshape, bparts):
    a = dd_matrix(64, seed=3)
    b = jnp.asarray(
        np.random.default_rng(0).standard_normal(bshape).astype(np.float32)
    )
    x = run_solve(a, b, lower=True, graph=graph, partitions=((4, 4),), b_partitions=bparts)
    want = solve_triangular(a, b, lower=True, unit_diagonal=True)
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
@pytest.mark.parametrize("bshape,bparts", [((64, 64), ((4, 4),)), ((32, 64), ((2, 4),))])
def test_solve_upper(graph, bshape, bparts):
    a = dd_matrix(64, seed=4)
    b = jnp.asarray(
        np.random.default_rng(1).standard_normal(bshape).astype(np.float32)
    )
    x = run_solve(a, b, lower=False, graph=graph, partitions=((4, 4),), b_partitions=bparts)
    # x @ triu(a) = b  <=>  triu(a)^T x^T = b^T
    want = solve_triangular(a, b.T, lower=False, trans="T").T
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("graph", ["g3", "g4"])
def test_solve_distributed(graph):
    a = dd_matrix(64, seed=6)
    b = jnp.asarray(
        np.random.default_rng(2).standard_normal((64, 32)).astype(np.float32)
    )
    x = run_solve(
        a, b, lower=True, graph=graph,
        partitions=((2, 2), (2, 2)), b_partitions=((2, 2), (2, 1)),
        mesh=_mesh_1d(),
    )
    want = solve_triangular(a, b, lower=True, unit_diagonal=True)
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
def test_solve_upper_left(graph):
    """TRSMUL — the fourth TRSM orientation: x = inv(triu(a)) @ b."""
    a = dd_matrix(64, seed=5)
    b = jnp.asarray(
        np.random.default_rng(4).standard_normal((64, 32)).astype(np.float32)
    )
    x = run_solve(
        a, b, lower=False, side="left", graph=graph,
        partitions=((4, 4),), b_partitions=((4, 2),),
    )
    want = solve_triangular(a, b, lower=False)
    np.testing.assert_allclose(np.asarray(x), np.asarray(want), atol=1e-5)


def test_solve_side_validation():
    a = dd_matrix(32, seed=1)
    b = jnp.zeros((32, 32), jnp.float32)
    with pytest.raises(ValueError, match="left"):
        run_solve(a, b, lower=True, side="right", partitions=((2, 2),))
    with pytest.raises(ValueError, match="side"):
        run_solve(a, b, lower=False, side="up", partitions=((2, 2),))


def test_lu_then_solve_round_trip():
    """Forward+backward substitution through the packed factor solves a@x=b."""
    n = 64
    a = dd_matrix(n, seed=8)
    b = jnp.asarray(
        np.random.default_rng(3).standard_normal((n, n)).astype(np.float32)
    )
    L, U = run_lu(a, graph="g2", partitions=((4, 4),))
    packed = jnp.tril(L, -1) + U
    np.testing.assert_allclose(np.asarray(L @ U), np.asarray(a), atol=1e-5)
    y = run_solve(packed, b, lower=True, partitions=((4, 4),))  # L y = b
    np.testing.assert_allclose(np.asarray(L @ y), np.asarray(b), atol=1e-4)
    # U x = y: the left-upper orientation (TRSMUL) completes the round trip
    x = run_solve(packed, y, lower=False, side="left", partitions=((4, 4),))
    np.testing.assert_allclose(np.asarray(a @ x), np.asarray(b), atol=1e-4)


# --------------------------------------------------------------------------
# run_lu_solve: the end-to-end factor+solve pipeline in ONE drain
# --------------------------------------------------------------------------
def _lu_solve_ref(a, b):
    # partial pivoting selects P == I on dd matrices (asserted by _lu_ref
    # elsewhere), so the pivoted library solve is directly comparable
    return scipy_lu_solve(lu_factor(a), b)


@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
@pytest.mark.parametrize(
    "bshape,bparts",
    [((64, 64), ((4, 4),)), ((64, 32), ((4, 2),)), ((64,), None)],
)
def test_lu_solve_single_level(graph, bshape, bparts):
    a = dd_matrix(64, seed=13)
    b = jnp.asarray(
        np.random.default_rng(5).standard_normal(bshape).astype(np.float32)
    )
    x = run_lu_solve(
        a, b, graph=graph, partitions=((4, 4),), b_partitions=bparts
    )
    assert x.shape == b.shape
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(_lu_solve_ref(a, b)), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(a @ x), np.asarray(b), atol=1e-4
    )


@pytest.mark.parametrize("graph", ["g3", "g4", "g3flat"])
def test_lu_solve_distributed_graphs(graph):
    n = 64
    a = dd_matrix(n, seed=14)
    b = jnp.asarray(
        np.random.default_rng(6).standard_normal((n, n)).astype(np.float32)
    )
    parts = ((2, 2), (2, 2)) if graph in ("g3", "g4") else ((4, 4),)
    x = run_lu_solve(a, b, graph=graph, partitions=parts, mesh=_mesh_1d())
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(_lu_solve_ref(a, b)), atol=1e-4
    )


def test_lu_solve_shape_mismatch():
    a = dd_matrix(32, seed=1)
    with pytest.raises(ValueError, match="mismatch"):
        run_lu_solve(a, jnp.zeros((16, 4), jnp.float32), partitions=((2, 2),))


def test_lu_solve_single_drain_compile_once():
    """The whole factor+solve pipeline is ONE WaveProgram: one launch and
    one compile on the first drain, pure replay (0 recompiles) on repeats —
    the acceptance criterion for the composed LUSOLVE workload."""
    clear_compile_cache()
    n, p = 64, 4
    stats = []
    for seed in (1, 2, 3):
        d = Dispatcher(graph="g2")
        A = GData((n, n), partitions=((p, p),), dtype=jnp.float32,
                  value=dd_matrix(n, seed=seed))
        B = GData(
            (n, n), partitions=((p, p),), dtype=jnp.float32,
            value=jnp.asarray(
                np.random.default_rng(seed)
                .standard_normal((n, n)).astype(np.float32)
            ),
        )
        utp_lu_solve(d, A, B)
        k = d.run()
        stats.append(
            (k, d.executor.stats.get("launches", 0),
             d.executor.stats.get("compiles", 0))
        )
    # leaf count: factor 30 (see test_repeated_lu_drains_compile_once)
    # + forward 40 + backward 40 block-substitution tasks at p = m = 4
    assert stats[0] == (110, 1, 1)
    for rep in stats[1:]:
        assert rep == (110, 1, 0)


@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
def test_run_inv(graph):
    n = 64
    a = dd_matrix(n, seed=15)
    inv = run_inv(a, graph=graph, partitions=((4, 4),))
    np.testing.assert_allclose(
        np.asarray(inv @ a), np.eye(n), atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(inv), np.asarray(jnp.linalg.inv(a)), atol=1e-4
    )


def test_lu_solve_ops_registered_and_memoizable():
    for name in ("trsmul", "lu_solve"):
        op = OpRegistry.get(name)
        assert op.memoizable  # geometry-pure splits ride the drain memo


# --------------------------------------------------------------------------
# Wave-program cache: repeated LU drains compile once (PR-1 drain memo)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("graph", ["g2", "g2p"])
def test_repeated_lu_drains_compile_once(graph):
    clear_compile_cache()
    stats = []
    for seed in (1, 2, 3):
        d = Dispatcher(graph=graph)
        A = GData((64, 64), partitions=((4, 4),), dtype=jnp.float32,
                  value=dd_matrix(64, seed=seed))
        utp_getrf(d, A)
        n = d.run()
        stats.append(
            (n, d.executor.stats.get("launches", 0),
             d.executor.stats.get("compiles", 0))
        )
    # 4x4 right-looking LU: sum_k 1 + 2*(3-k) + (3-k)^2 = 16+9+4+1 = 30
    assert stats[0] == (30, 1, 1)  # one compiled WaveProgram, one dispatch
    for rep in stats[1:]:
        assert rep == (30, 1, 0)  # replayed drains: 0 recompiles


def test_lu_ops_registered_and_memoizable():
    for name in ("getrf", "trsml", "trsmu", "gemmnn"):
        op = OpRegistry.get(name)
        assert op.memoizable  # geometry-pure splits ride the drain memo


# --------------------------------------------------------------------------
# Satellite: utp_get_parameters rejects non-positive sizes/partitions
# --------------------------------------------------------------------------
def test_utp_get_parameters_accepts_positive():
    assert utp_get_parameters(["1024", "8", "4"]) == (1024, 8, 4)
    assert utp_get_parameters([]) == (1024, 4, 4)


@pytest.mark.parametrize("argv", [["-4"], ["1024", "-8"], ["1024", "8", "0"], ["0"]])
def test_utp_get_parameters_rejects_nonpositive(argv):
    with pytest.raises(ValueError, match="positive"):
        utp_get_parameters(argv)
