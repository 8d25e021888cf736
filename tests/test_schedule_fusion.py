"""Dependency-exact scheduling: cross-wave fusion + lookahead (DESIGN.md §2).

Covers: the fusion-legality query (``TaskDag.independent``), hypothesis
property tests on random task DAGs (the dependency-exact schedule is a
valid topological order, every fused group is edge-free internally, and
slot-launch semantics match the sequential program order exactly),
multi-root drains (LU of A + Cholesky of B in one compiled program; LU + LU
fusing same-signature groups across roots into shared launches), lookahead
ordering inside issue slots, and the plan-time flat index array that replay
reuses device-resident.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import Access, Dispatcher, DepTracker, GData, GTask, Operation
from repro.core import dd_matrix, spd_matrix
from repro.core.executors import (
    clear_compile_cache,
    drain_memo_stats,
    plan_schedule,
)
from repro.core.executors.jit_wave import _DRAIN_MEMO
from repro.linalg import run_cholesky, run_lu, run_lu_many
from repro.linalg.cholesky import utp_cholesky
from repro.linalg.lu import utp_getrf, utp_lu_solve, utp_solve
from repro.testing import faults


# --------------------------------------------------------------------------
# Fusion-legality query (versioning.TaskDag)
# --------------------------------------------------------------------------
class _Nop(Operation):
    def __init__(self, modes):
        self._modes = list(modes)
        self.name = "nop_" + "".join(m.value[0] for m in self._modes)

    def default_modes(self, n):
        return list(self._modes)


_NOPS = {}


def mktask(data, accesses):
    """accesses: list of ((r, c), Access); ops shared per modes tuple so
    same-mode tasks share a signature (as registered singletons would)."""
    modes = tuple(m for _, m in accesses)
    op = _NOPS.setdefault(modes, _Nop(modes))
    views = [data(r, c) for (r, c), _ in accesses]
    return GTask(op, None, views, list(modes))


def _track(tasks):
    tr = DepTracker()
    for t in tasks:
        tr.add(t)
    return tr


def test_independent_query_basics():
    A = GData((8, 8), partitions=((2, 2),))
    w = mktask(A, [((0, 0), Access.WRITE)])
    r = mktask(A, [((0, 0), Access.READ)])
    other = mktask(A, [((1, 1), Access.WRITE)])
    dag = _track([w, r, other]).dag()
    assert not dag.independent([w.id], [r.id])  # RAW path
    assert dag.independent([w.id], [other.id])  # disjoint blocks
    assert dag.independent([w.id, other.id], [w.id, other.id])  # edge-free set
    assert not dag.independent([w.id, r.id], [w.id, r.id])  # internal edge


def test_independent_sees_transitive_paths():
    A = GData((8, 8), partitions=((2, 2),))
    t1 = mktask(A, [((0, 0), Access.WRITE)])
    t2 = mktask(A, [((0, 0), Access.READ), ((0, 1), Access.WRITE)])
    t3 = mktask(A, [((0, 1), Access.READ), ((1, 1), Access.WRITE)])
    dag = _track([t1, t2, t3]).dag()
    assert not dag.independent([t1.id], [t3.id])  # only via t2


def test_heights_follow_critical_path():
    A = GData((8, 8), partitions=((2, 2),))
    chain = [mktask(A, [((0, 0), Access.READWRITE)]) for _ in range(3)]
    lone = mktask(A, [((1, 1), Access.WRITE)])
    dag = _track(chain + [lone]).dag()
    h = dag.heights()
    assert h[chain[0].id] == 2 and h[chain[2].id] == 0 and h[lone.id] == 0


# --------------------------------------------------------------------------
# Multi-root drains (ROADMAP item): independent workloads share one program
# --------------------------------------------------------------------------
def test_multiroot_lu_and_cholesky_one_drain():
    clear_compile_cache()
    n, p = 64, 4
    a = dd_matrix(n, seed=11)
    b = spd_matrix(n, seed=12)
    ref_l, ref_u = run_lu(a, partitions=((p, p),))
    ref_c = run_cholesky(b, partitions=((p, p),))
    clear_compile_cache()

    def drain():
        d = Dispatcher(graph="g2")
        A = GData(a.shape, partitions=((p, p),), dtype=a.dtype, value=a)
        B = GData(b.shape, partitions=((p, p),), dtype=b.dtype, value=b)
        utp_getrf(d, A)
        utp_cholesky(d, B)
        n_leaf = d.run()
        return d, A, B, n_leaf

    d1, A1, B1, n1 = drain()
    # both workloads interleave into ONE compiled program / ONE launch
    assert d1.executor.stats["launches"] == 1
    assert d1.executor.stats["compiles"] == 1
    packed = np.asarray(A1.value)
    np.testing.assert_allclose(
        np.tril(packed, -1) + np.eye(n), np.asarray(ref_l), rtol=1e-6
    )
    np.testing.assert_allclose(np.triu(packed), np.asarray(ref_u), rtol=1e-6)
    np.testing.assert_allclose(
        np.tril(np.asarray(B1.value)), np.asarray(ref_c), rtol=1e-6
    )
    # structurally repeated combined drain: memo replay, 0 recompiles
    d2, A2, B2, n2 = drain()
    assert n2 == n1
    assert d2.stats["split"] == d1.stats["split"]  # replay mirrors stats
    assert d2.executor.stats["launches"] == 1
    assert d2.executor.stats.get("compiles", 0) == 0
    np.testing.assert_allclose(np.asarray(A2.value), packed, rtol=1e-6)


def test_multiroot_lu_pair_fuses_groups_across_roots():
    clear_compile_cache()
    n, p = 64, 4
    a = dd_matrix(n, seed=21)
    b = dd_matrix(n, seed=22)
    # stack_roots=False pins the PR-3 segment-fusion path: a homogeneous
    # pair would otherwise take the stacked batched-program path
    # (DESIGN.md §7, tests/test_stacked_drain.py)
    d = Dispatcher(graph="g2", stack_roots=False)
    A = GData(a.shape, partitions=((p, p),), dtype=a.dtype, value=a)
    B = GData(b.shape, partitions=((p, p),), dtype=b.dtype, value=b)
    utp_getrf(d, A)
    utp_getrf(d, B)
    d.run()
    st = d.executor.stats
    assert st["launches"] == 1
    # the two independent LU DAGs run in SHARED launches: the fused group
    # count equals one workload's (every group carries both roots' tasks)
    # and is strictly below the pre-fusion barrier-wave group count
    assert st["groups"] < st["groups_prefusion"]
    assert st["groups_prefusion"] == 2 * st["groups"]
    # numerics match the single-root reference factorizations
    for M, m in ((A, a), (B, b)):
        packed = np.asarray(M.value)
        l = np.tril(packed, -1) + np.eye(n)
        u = np.triu(packed)
        np.testing.assert_allclose(l @ u, np.asarray(m), rtol=2e-4, atol=2e-4)


def test_run_lu_many_replays_with_zero_recompiles():
    clear_compile_cache()
    n, p = 64, 4
    mats = [dd_matrix(n, seed=s) for s in (31, 32)]
    outs1 = run_lu_many(mats, partitions=((p, p),))
    # structurally repeated multi-root drain on fresh values: pure replay
    mats2 = [dd_matrix(n, seed=s) for s in (33, 34)]
    misses = drain_memo_stats()["misses"]
    with faults.inject("executor.launch", record=True, times=None) as probe:
        outs2 = run_lu_many(mats2, partitions=((p, p),))
    # one launch, and it replays the captured program: nothing recompiles
    assert [e["replay"] for e in probe.log] == [True]
    assert drain_memo_stats()["misses"] == misses
    for (l, u), m in zip(outs1 + outs2, mats + mats2):
        np.testing.assert_allclose(
            np.asarray(l) @ np.asarray(u), np.asarray(m), rtol=2e-4, atol=2e-4
        )
    # the second drain hit the drain memo (captured by the first)
    assert len(_DRAIN_MEMO) >= 1


def test_lu_solve_overlaps_solve_groups_with_factor_groups():
    """The composed factor+solve drain (DESIGN.md §4): ONE WaveProgram where
    the dependency-exact pass (a) fuses solve groups into independent
    same-signature factor groups (row-i forward substitutions share a slot
    with step-i panel solves — unlike single-root LU, the combined DAG has
    slack) and (b) schedules the pipeline in strictly fewer issue slots
    than the three barrier-separated drains need in total."""
    clear_compile_cache()
    n, p = 64, 4
    a = dd_matrix(n, seed=51)
    b = jnp.asarray(
        np.random.default_rng(7).standard_normal((n, n)).astype(np.float32)
    )

    def fresh(val):
        return GData(val.shape, partitions=((p, p),), dtype=val.dtype, value=val)

    # baseline: factor, forward solve, backward solve as separate drains
    d1 = Dispatcher(graph="g2")
    A1 = fresh(a)
    utp_getrf(d1, A1)
    d1.run()
    packed = A1.value
    d2 = Dispatcher(graph="g2")
    A2, B2 = fresh(packed), fresh(b)
    utp_solve(d2, A2, B2, lower=True)
    d2.run()
    d3 = Dispatcher(graph="g2")
    A3, B3 = fresh(packed), fresh(B2.value)
    utp_solve(d3, A3, B3, lower=False, side="left")
    d3.run()
    separate_slots = sum(d.executor.stats["slots"] for d in (d1, d2, d3))
    separate_groups = sum(d.executor.stats["groups"] for d in (d1, d2, d3))

    # composed: the same pipeline as ONE LUSOLVE root -> one WaveProgram
    d = Dispatcher(graph="g2")
    A, B = fresh(a), fresh(b)
    utp_lu_solve(d, A, B)
    d.run()
    st = d.executor.stats
    assert st["launches"] == 1 and st["compiles"] == 1
    # solve groups fused into factor groups: single-root lu_solve has slack
    # (contrast test_single_root_lu_is_at_its_chain_lower_bound below)
    assert st["groups"] < st["groups_prefusion"]
    assert st["groups"] < separate_groups
    # overlap: solve slots interleave with late factor slots instead of
    # queueing behind them
    assert st["slots"] < separate_slots
    # and the composed drain computes the same x as the staged pipeline
    np.testing.assert_allclose(
        np.asarray(B.value), np.asarray(B3.value), rtol=2e-4, atol=2e-4
    )
    # a structurally repeated composed drain replays: one launch, 0 compiles
    d = Dispatcher(graph="g2")
    utp_lu_solve(d, fresh(dd_matrix(n, seed=52)), fresh(b))
    d.run()
    assert d.executor.stats["launches"] == 1
    assert d.executor.stats.get("compiles", 0) == 0


def test_single_root_lu_is_at_its_chain_lower_bound():
    """Honest negative: single-matrix LU's same-signature chains (GETRF ->
    ... -> GETRF, per-C-block GEMMNN chains) make every Kahn group minimal,
    so fusion must NOT merge anything — the group-count win is multi-root
    (above); merging here would be a legality bug (DESIGN.md §2)."""
    clear_compile_cache()
    n, p = 64, 4
    a = dd_matrix(n, seed=41)
    d = Dispatcher(graph="g2")
    A = GData(a.shape, partitions=((p, p),), dtype=a.dtype, value=a)
    utp_getrf(d, A)
    d.run()
    st = d.executor.stats
    assert st["groups"] == st["groups_prefusion"] == 3 * (p - 1) + p


# --------------------------------------------------------------------------
# Lookahead: critical-path-first ordering inside an issue slot
# --------------------------------------------------------------------------
def test_lookahead_orders_critical_group_first():
    A = GData(
        (16, 16),
        partitions=((4, 4),),
        value=np.zeros((16, 16), dtype=np.float32),
    )
    # slot 0: a long chain head on block (0,0) vs trailing one-shot writes;
    # the chain head must be traced first despite later submission order
    trailing = [mktask(A, [((i, j), Access.WRITE)]) for i, j in ((2, 2), (3, 3))]
    chain = [mktask(A, [((0, 0), Access.READWRITE), ((1, 1), Access.WRITE)])]
    chain += [mktask(A, [((0, 0), Access.READWRITE)]) for _ in range(3)]
    tasks = trailing + chain  # trailing submitted first
    tr = _track(tasks)
    plan = plan_schedule(tr.waves(), tr.dag())
    first_slot = plan.slots[0]
    assert len(first_slot) == 2
    heights = [g.height for g in first_slot]
    assert heights == sorted(heights, reverse=True)
    # the chain head group (height 3) leads the trailing group (height 0)
    assert first_slot[0].height == 3 and first_slot[-1].height == 0
