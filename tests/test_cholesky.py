"""The paper's experimental vehicle: blocked Cholesky through every
task-flow graph must match jnp.linalg.cholesky (paper Fig. 2/3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import GRAPHS, Dispatcher, GData, spd_matrix
from repro.linalg import run_cholesky


def _mesh_1d():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("graph", ["g1", "g2", "g2p"])
@pytest.mark.parametrize("n,parts", [(32, ((2, 2),)), (64, ((4, 4),))])
def test_cholesky_single_level(graph, n, parts):
    a = spd_matrix(n, seed=n)
    L = run_cholesky(a, graph=graph, partitions=parts)
    np.testing.assert_allclose(L, jnp.linalg.cholesky(a), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("graph", ["g3", "g4", "g3flat"])
def test_cholesky_distributed_graphs(graph):
    n = 64
    a = spd_matrix(n, seed=7)
    parts = ((2, 2), (2, 2)) if graph in ("g3", "g4") else ((4, 4),)
    L = run_cholesky(a, graph=graph, partitions=parts, mesh=_mesh_1d())
    np.testing.assert_allclose(L, jnp.linalg.cholesky(a), rtol=2e-4, atol=2e-4)


def test_hierarchical_two_level_matches_flat():
    """DuctTeip-over-SuperGlue hierarchy == flat (paper C5 vs C6 semantics)."""
    a = spd_matrix(64, seed=9)
    flat = run_cholesky(a, graph="g2", partitions=((4, 4),))
    hier = run_cholesky(a, graph="g3", partitions=((2, 2), (2, 2)), mesh=_mesh_1d())
    np.testing.assert_allclose(flat, hier, rtol=1e-5, atol=1e-5)


def test_same_program_all_graphs_identical_results():
    """The paper's portability claim: ONE program, any graph, same result."""
    a = spd_matrix(32, seed=11)
    outs = {}
    for g in ("g1", "g2", "g2p"):
        outs[g] = np.asarray(run_cholesky(a, graph=g, partitions=((2, 2),)))
    base = outs["g1"]
    for g, v in outs.items():
        np.testing.assert_allclose(v, base, rtol=1e-5, atol=1e-5)


def test_dispatcher_stats():
    a = spd_matrix(32, seed=3)
    d_stats = {}
    from repro.linalg.cholesky import utp_cholesky

    d = Dispatcher(graph="g2")
    A = GData(a.shape, partitions=((4, 4),), dtype=a.dtype, value=a)
    utp_cholesky(d, A)
    n = d.run()
    # 4x4 blocked cholesky: sum_i [i syrk + i*(3-i) gemm + 1 potrf + (3-i) trsm]
    # = 4 + 6 + 6 + 4 = 20 leaf tasks
    assert n == 20
    assert d.stats["submitted"] == 1
    assert d.stats["split"] == 1
