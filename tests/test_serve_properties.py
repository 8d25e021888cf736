"""Hypothesis property test: stacked drains match independent drains.

For random batch sizes (pow2 and not), geometries, and task-flow graphs,
every per-request result of one stacked batched drain must match the same
request run as its own independent drain.  Tolerance note: the stacked
program compiles DIFFERENT XLA programs (leaf stacks of size B*s instead
of s), so bit-exactness across the two compilations is not guaranteed by
XLA; observed differences are ~1 ulp and the assertion uses a 1e-6
tolerance several orders tighter than the factorization's own error.

Separate module from test_serve so the property machinery stays out of
the deterministic serving tests' import path.
"""

import numpy as np

from repro.core import dd_matrix, spd_matrix
from repro.core.executors import clear_compile_cache
from repro.linalg import (
    run_cholesky,
    run_lu,
    run_lu_batched,
    run_lu_solve,
    run_lu_solve_batched,
)
from repro.serve import BatchServer

from hypothesis import given, settings, strategies as st


@settings(max_examples=12, deadline=None)
@given(
    n_roots=st.integers(1, 6),
    geom=st.sampled_from([(32, 2), (32, 4), (64, 4)]),
    graph=st.sampled_from(["g1", "g2"]),
    seed=st.integers(0, 1000),
)
def test_stacked_lu_matches_independent_drains(n_roots, geom, graph, seed):
    n, p = geom
    mats = [dd_matrix(n, seed=seed + s) for s in range(n_roots)]
    clear_compile_cache()
    stacked = run_lu_batched(mats, graph=graph, partitions=((p, p),))
    clear_compile_cache()
    singles = [run_lu(m, graph=graph, partitions=((p, p),)) for m in mats]
    for (ls, us), (li, ui) in zip(stacked, singles):
        np.testing.assert_allclose(
            np.asarray(ls), np.asarray(li), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(us), np.asarray(ui), rtol=1e-6, atol=1e-6
        )


@settings(max_examples=8, deadline=None)
@given(
    n_roots=st.integers(1, 5),
    m_cols=st.sampled_from([1, 4]),
    graph=st.sampled_from(["g1", "g2"]),
    seed=st.integers(0, 1000),
)
def test_stacked_lu_solve_matches_independent_drains(
    n_roots, m_cols, graph, seed
):
    n, p = 32, 4
    rng = np.random.default_rng(seed)
    mats = [dd_matrix(n, seed=seed + s) for s in range(n_roots)]
    rhss = [
        rng.standard_normal((n, m_cols)).astype(np.float32)
        for _ in range(n_roots)
    ]
    clear_compile_cache()
    stacked = run_lu_solve_batched(
        mats, rhss, graph=graph, partitions=((p, p),), b_partitions=((p, 1),)
    )
    clear_compile_cache()
    singles = [
        run_lu_solve(
            a, b, graph=graph, partitions=((p, p),), b_partitions=((p, 1),)
        )
        for a, b in zip(mats, rhss)
    ]
    for xs, xi in zip(stacked, singles):
        np.testing.assert_allclose(
            np.asarray(xs), np.asarray(xi), rtol=1e-6, atol=1e-6
        )


# -- mixed-signature traffic ---------------------------------------------------

_N, _P = 32, 2
_KINDS = ("lu", "cholesky", "lu_solve")


def _rhs(seed: int) -> np.ndarray:
    return np.random.default_rng(1000 + seed).standard_normal(_N).astype(
        np.float32
    )


def _submit(srv: BatchServer, kind: str, seed: int):
    if kind == "lu":
        return srv.lu(dd_matrix(_N, seed=seed), partitions=((_P, _P),))
    if kind == "cholesky":
        return srv.cholesky(spd_matrix(_N, seed=seed), partitions=((_P, _P),))
    return srv.lu_solve(
        dd_matrix(_N, seed=seed), _rhs(seed), partitions=((_P, _P),)
    )


def _sequential(kind: str, seed: int):
    """The same request as its own independent drain (no serving layer)."""
    if kind == "lu":
        return run_lu(dd_matrix(_N, seed=seed), partitions=((_P, _P),))
    if kind == "cholesky":
        return run_cholesky(spd_matrix(_N, seed=seed), partitions=((_P, _P),))
    return run_lu_solve(
        dd_matrix(_N, seed=seed),
        _rhs(seed),
        partitions=((_P, _P),),
        b_partitions=((_P, 1),),
    )


def _leaves(result):
    return list(result) if isinstance(result, tuple) else [result]


@st.composite
def traffic(draw):
    """A few ticks of mixed lu/cholesky/lu_solve traffic, each tick's
    submission order an arbitrary interleaving of the three signatures."""
    ticks = []
    for _ in range(draw(st.integers(1, 3))):
        reqs = []
        for kind in _KINDS:
            for _ in range(draw(st.integers(0, 3))):
                reqs.append((kind, draw(st.integers(0, 50))))
        order = draw(st.permutations(list(range(len(reqs)))))
        ticks.append([reqs[i] for i in order])
    return ticks


@settings(max_examples=5, deadline=None)
@given(plan=traffic(), overlap=st.booleans())
def test_mixed_signature_traffic_matches_sequential(plan, overlap):
    """Random interleavings of mixed-signature submits across ticks must
    resolve every future (a) BIT-identically to the canonical server that
    sees the same requests per tick in signature-grouped order (lane
    position and submission interleaving cannot change a request's bits —
    same bucket multiset => same stacked program, lanes independent), with
    ``overlap`` on and off, and (b) numerically equal (1e-6, the DESIGN.md
    §7 stacked-vs-single tolerance: different XLA programs) to the same
    request drained sequentially on its own."""
    clear_compile_cache()
    srv = BatchServer(graph="g2", overlap=overlap)
    canon = BatchServer(graph="g2", overlap=False)
    subject = []  # (kind, seed, future)
    canon_futs = {}  # (kind, seed) -> [futures]
    for tick in plan:
        for kind, seed in tick:
            subject.append((kind, seed, _submit(srv, kind, seed)))
        for kind, seed in sorted(tick, key=lambda r: _KINDS.index(r[0])):
            canon_futs.setdefault((kind, seed), []).append(
                _submit(canon, kind, seed)
            )
        rep = srv.tick()
        canon.tick()
        assert rep.resolved == len(tick) and rep.failed == 0
    for kind, seed, fut in subject:
        got = _leaves(fut.result())
        want_bits = _leaves(canon_futs[(kind, seed)].pop().result())
        for g, w in zip(got, want_bits):
            assert np.array_equal(np.asarray(g), np.asarray(w)), (
                f"{kind}(seed={seed}): interleaved result != canonical "
                f"signature-grouped result (bit-identity)"
            )
        for g, w in zip(got, _leaves(_sequential(kind, seed))):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6,
                err_msg=f"{kind}(seed={seed}) vs sequential drain",
            )
